"""chip_smoke.py at micro size on the CPU (fast tier).

The smoke itself only runs on a TPU; its steps are functions of their
workloads and sizes, so tier-1 drives every one of them here on the
2-node x 6-pod micro workload — through the same entry points, over the
8-virtual-device mesh where the step has a mesh path — and pins the two
ways the script must refuse to produce a result: no chip, and a step
whose check fails.
"""
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # `import chip_smoke` regardless of pytest rootdir
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from fks_tpu import cli  # noqa: E402
from fks_tpu.parallel import population_mesh  # noqa: E402


@pytest.fixture
def micro_cli(micro_workload, monkeypatch):
    monkeypatch.setattr(cli, "_parse_workload",
                        lambda args: ("micro", micro_workload))
    return micro_workload


def test_exits_nonzero_and_prints_nothing_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_step_parity_micro(micro_cli, tmp_path):
    out = chip_smoke.step_parity("micro", "micro",
                                 {"first_fit": None, "best_fit": None},
                                 n_pods=6, out_dir=str(tmp_path))
    assert out["ok"], out
    assert out["policies"]["best_fit"]["scheduled"] == 6
    # a wrong expectation fails the step instead of being waved through
    bad = chip_smoke.step_parity("micro", "micro", {"best_fit": (0.9, 1)},
                                 n_pods=6, out_dir=str(tmp_path))
    assert not bad["ok"] and bad["mismatch"] == ["best_fit"]


def test_step_evaluate_parametric_micro_over_the_mesh(micro_workload):
    mesh = population_mesh(jax.devices())
    out = chip_smoke.step_evaluate_parametric(micro_workload, 16, mesh)
    assert out["ok"], out
    assert len(out["lanes_per_device"]) == len(jax.devices())
    assert set(out["lanes_per_device"].values()) == {2}
    # the exact-engine equality arm: a wrong reference fails the step
    bad = chip_smoke.step_evaluate_parametric(micro_workload, 8,
                                              exact_best_fit=0.123)
    assert not bad["ok"]


def test_step_evaluate_code_micro_over_the_mesh(micro_workload):
    from fks_tpu.funsearch import FakeLLM, template

    seeds = template.seed_policies()
    fake = FakeLLM(seed=0, junk_rate=0.0)
    checked = {n: (c, None) for n, c in seeds.items()}
    checked["drafted"] = (template.fill_template(fake.complete("")), None)
    mesh = population_mesh(jax.devices())
    out = chip_smoke.step_evaluate_code(micro_workload, checked, mesh)
    assert out["ok"], out
    assert out["vm_batch"] and out["fallback_lanes"] == 0
    assert len(out["lanes_per_device"]) == len(jax.devices())
    # five candidates over eight devices: the evaluator pads to two lanes
    # per device, never to the batch-of-one program (vm.bucket_lanes)
    assert set(out["lanes_per_device"].values()) == {2}
    got = out["scores"]["best_fit"]
    checked["best_fit"] = (seeds["best_fit"], got + 0.01)
    bad = chip_smoke.step_evaluate_code(micro_workload, checked, mesh)
    assert not bad["ok"]


def test_step_evaluate_forked_micro(micro_workload):
    import dataclasses

    from fks_tpu.models import zoo
    from fks_tpu.sim import flat

    forked = dataclasses.replace(micro_workload, snapshot=flat.make_snapshot(
        micro_workload, zoo.best_fit(), 2))
    out = chip_smoke.step_evaluate_forked(forked, 3, {},
                                          placed_by=zoo.best_fit())
    assert out["ok"], out
    assert out["carry_leaves_differing"] == []
    # residents placed by another policy than the one named: not its carry
    other = chip_smoke.step_evaluate_forked(forked, 3, {},
                                            placed_by=zoo.first_fit())
    assert not other["ok"] and "cpu_left" in other["carry_leaves_differing"]
    assert (out["start_event"], out["residents"]) == (2, 2)
    assert {v["events"] for v in out["lanes"].values()} == {5}
    got = out["lanes"]["best_fit"]
    # a wrong expectation fails the step instead of being waved through
    bad = chip_smoke.step_evaluate_forked(
        forked, 3, {"best_fit": (got["scheduled"] + 1, 0)})
    assert not bad["ok"] and bad["mismatch"][0]["policy"] == "best_fit"


def test_step_evolve_micro(micro_cli, tmp_path):
    out = chip_smoke.step_evolve(str(tmp_path), generations=1,
                                 population_size=7)
    assert out["ok"], out
    assert out["rescore_fallbacks"] == 0
    assert out["rescore_platform"] == "cpu"
    assert out["ledger_untouched"]


def test_step_serve_micro(micro_workload):
    from fks_tpu import obs
    from fks_tpu.funsearch import template
    from fks_tpu.serve import ChampionSpec

    champs = [ChampionSpec(code=template.fill_template(lg), score=s)
              for lg, s in (("score = 1000", 0.5),
                            ("score = node.cpu_milli_left - pod.cpu_milli",
                             0.4))]
    with obs.CompileWatcher() as watcher:
        out = chip_smoke.step_serve(micro_workload, micro_workload, champs,
                                    [3, 5], [2], watcher, portfolio_pods=3)
    assert out["ok"], out
    assert out["A"]["second_pass_compiles"] == 0
    assert out["A"]["max_drift"] == 0.0
    assert not out["A"]["degraded_fallback_armed"]
    assert out["portfolio"]["n_slots"] == 2


def test_served_score_bound_is_a_few_ulps_of_the_score():
    """One f32 ulp at B's 0.0034 (the drift four chips showed) passes; an
    absolute 1e-6 there, about 4,000 ulps, does not."""
    ref = 0.003429
    assert chip_smoke._score_agrees(ref + 2.3283064365386963e-10, ref)
    assert chip_smoke._score_agrees(ref, ref)
    assert not chip_smoke._score_agrees(ref + 1e-8, ref)
    assert not chip_smoke._score_agrees(0.342081 + 1e-6, 0.342081)


def test_step_fused_micro_in_interpret_mode(micro_workload):
    out = chip_smoke.step_fused(micro_workload, lanes=8, interpret=True)
    assert out["ok"], out
    assert out["scheduled_equal"]


def test_last_stdout_line_is_the_verdict_and_nothing_else(capsys):
    """The chip check reads the LAST stdout line and wants exactly
    ``{"ok", "device": {"platform", "kind", "count"}}``; the summary with
    the timings and ``"claim": null`` is the line before it. A failed
    step gives ``"ok": false``, exit 1, and stops the run."""
    import json

    from fks_tpu import obs

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    ran = []

    def step(name, ok):
        return name, lambda: ran.append(name) or {"ok": ok}

    rc = chip_smoke.run_steps([step("a", True), step("b", True)],
                              obs.CompileWatcher().install(), device)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and [json.loads(ln)["step"] for ln in lines[:-1]] == [
        "a", "b", "summary"]
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert lines[-2].endswith('"claim": null}')
    assert isinstance(json.loads(lines[-1])["device"]["count"], int)

    ran.clear()
    rc = chip_smoke.run_steps(
        [step("a", False), step("b", True)],
        obs.CompileWatcher().install(), device, partial=True)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 1 and ran == ["a"]
    assert json.loads(lines[-1]) == {"ok": False, "device": device}
    summary = json.loads(lines[-2])
    assert summary["failed_step"] == "a" and summary["partial"]

    # a step that raises is a failed step, not a crash without a verdict
    def boom():
        raise RuntimeError("device fault")

    rc = chip_smoke.run_steps([("c", boom)], obs.CompileWatcher().install(),
                              device)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 1 and json.loads(lines[-1]) == {"ok": False,
                                                 "device": device}
    assert "device fault" in json.loads(lines[0])["check"]["error"]


def test_summary_sources_are_tracked_files():
    """What the smoke reads must be in the checkout the chip tool copies:
    the audit row it checks against and the champion ledger."""
    recorded = chip_smoke._audit_flat_scores(chip_smoke.PODS)
    assert {"first_fit", "best_fit"} <= set(recorded)
    checked = chip_smoke._generation_sources(chip_smoke.PODS)
    assert len(checked) == 5
    assert all(want is not None for _, want in checked.values())
    tracked = subprocess.run(
        ["git", "-C", REPO, "ls-files", "benchmarks/results", "policies"],
        capture_output=True, text=True).stdout
    if tracked:  # the driver's checkout may not be a git repository
        assert "benchmarks/results/divergence_audit.jsonl" in tracked
        assert "policies/discovered/" in tracked
