"""bench.py wiring (fast tier): the code-candidate throughput stage runs
in-process on the conftest 8-virtual-device mesh, and the controller's
result-line contract — a number only from the run that measured it, with
its device beside it; no accelerator or a failed stage is a non-zero exit
with NO result line (see the bench.py module docstring).

The heavy stages (flat/fused parametric throughput) need the full trace
and a chip; here the codetput stage is routed to the micro workload so
its wiring — candidate sourcing via ``vm.lower_fake_candidates``, the
sharded dispatch, the JSON contract — stops being device-only code.
"""
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # `import bench` regardless of pytest rootdir
    sys.path.insert(0, REPO)

import bench  # noqa: E402


class _MicroParser:
    def __init__(self, wl):
        self._wl = wl

    def parse_workload(self, *a, **k):
        return self._wl


def test_stage_codetput_sharded_smoke(micro_workload, monkeypatch, capsys):
    """The stage sources FakeLLM candidates, shards them over the 8-device
    mesh, and prints the {"code_evals_per_sec": ...} JSON line."""
    import fks_tpu.data

    monkeypatch.setattr(fks_tpu.data, "TraceParser",
                        lambda: _MicroParser(micro_workload))
    monkeypatch.setenv("FKS_BENCH_CODE_POP", "2")
    assert bench.stage_codetput() == 0
    out = capsys.readouterr().out
    payload = json.loads(out.strip().splitlines()[-1])
    assert payload["code_evals_per_sec"] > 0
    assert payload["mode"] == "sharded over 8 devices"


def test_stage_codetput_gates_on_candidate_count(micro_workload, monkeypatch):
    """Fewer VM-able candidates than the stage needs -> rc 1 (and a
    failed controller run), not a crash or a fabricated number."""
    import fks_tpu.data
    from fks_tpu.funsearch import vm

    monkeypatch.setattr(fks_tpu.data, "TraceParser",
                        lambda: _MicroParser(micro_workload))
    monkeypatch.setattr(vm, "lower_fake_candidates",
                        lambda *a, **k: ([], []))
    assert bench.stage_codetput() == 1


def test_no_result_line_without_an_accelerator(monkeypatch, capsys):
    """No device, no number: on a CPU-only host the controller exits
    non-zero and stdout stays empty — no carried-forward headline, no
    banked session value, nothing a driver could parse as a result."""
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.delenv("FKS_RUN_DIR", raising=False)
    assert bench.main() == 1
    cap = capsys.readouterr()
    assert cap.out == ""
    assert "no accelerator" in cap.err


def test_failed_stage_fails_the_run_with_no_number(monkeypatch, capsys):
    """A failed stage is a failure: no engine fallback chain, no chunk
    quartering, no substituted code-throughput number."""
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    monkeypatch.setattr(bench, "_device_fields", lambda: {
        "platform": "tpu", "device_kind": "test", "device_count": 1})
    calls = []
    monkeypatch.setattr(bench, "stage_parity", lambda engine: 0)

    def boom(pop, chunk, reps, engine):
        calls.append(engine)
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(bench, "measure_throughput", boom)
    monkeypatch.setenv("FKS_BENCH_ENGINE", "fused")
    assert bench.main() == 1
    assert calls == ["fused"]  # one engine, named, tried once
    cap = capsys.readouterr()
    assert cap.out == ""
    assert "Mosaic refused the kernel" in cap.err
    # the code stage failing is as fatal as the headline stage
    monkeypatch.setattr(bench, "measure_throughput",
                        lambda *a: {"evals_per_sec": 50.0})
    monkeypatch.setattr(bench, "measure_codetput", lambda: None)
    assert bench.main() == 1
    assert capsys.readouterr().out == ""


def test_headline_line_names_its_device(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    device = {"platform": "tpu", "device_kind": "TPU v5 lite",
              "device_count": 1}
    monkeypatch.setattr(bench, "_device_fields", lambda: device)
    monkeypatch.setattr(bench, "stage_parity", lambda engine: 0)
    monkeypatch.setattr(bench, "measure_throughput",
                        lambda *a: {"evals_per_sec": 50.0,
                                    "compile_seconds": 2.5})
    monkeypatch.setattr(bench, "measure_codetput",
                        lambda: {"code_evals_per_sec": 0.25})
    assert bench.main() == 0
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload["value"] == 50.0 and payload["vs_baseline"] == 1.25
    assert {k: payload[k] for k in device} == device
    assert payload["engine"] == "flat"
    assert payload["code_evals_per_sec"] == 0.25
    for gone in ("stale_from_run", "banked_from", "code_source", "note"):
        assert gone not in payload


def test_cpu_pinned_stages_say_so_in_their_line():
    """Ten standalone stages pin the CPU backend; until ROADMAP S0
    replaces them, the line each prints carries ``"platform": "cpu"`` so
    no CPU timing leaves under a device metric's name."""
    import ast
    import inspect

    pinned = 0
    for name, fn in inspect.getmembers(bench, inspect.isfunction):
        if not name.startswith("stage_"):
            continue
        src = inspect.getsource(fn)
        if '"jax_platforms", "cpu"' not in src:
            continue
        pinned += 1
        tree = ast.parse(src)
        dicts = [n.value for n in ast.walk(tree)
                 if isinstance(n, ast.Assign)
                 and getattr(n.targets[0], "id", "") == "payload"
                 and isinstance(n.value, ast.Dict)]
        assert dicts, name
        keys = {k.value: v.value for k, v in zip(dicts[0].keys,
                                                 dicts[0].values)
                if isinstance(k, ast.Constant)
                and isinstance(v, ast.Constant)}
        assert keys.get("platform") == "cpu", name
    assert pinned == 10


def test_gate_judges_headline_against_baseline(tmp_path, capsys):
    baseline = tmp_path / "baseline.jsonl"
    baseline.write_text(json.dumps(
        {"value": 100.0, "unit": "evals/s"}) + "\n")
    ok = bench._gate(str(baseline), {"value": 95.0, "unit": "evals/s"})
    reg = bench._gate(str(baseline), {"value": 70.0, "unit": "evals/s"})
    err = capsys.readouterr().err
    assert ok == 0 and reg == 1
    assert "REGRESSION" in err
    # a broken gate (missing baseline) fails closed without raising
    assert bench._gate(str(tmp_path / "nope.jsonl"), {"value": 1.0}) == 1
