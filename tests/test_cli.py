"""CLI wiring tests (the heavy paths — full-trace bench/evolve — are
exercised by the engine/evolution suites; here we check the argparse
surface, discovery, and error handling)."""
import pytest

from fks_tpu import cli


def test_traces_lists_dataset(capsys):
    assert cli.main(["traces"]) == 0
    out = capsys.readouterr().out
    assert "openb_pod_list_default.csv" in out
    assert "openb_node_list_gpu_node.csv" in out


def test_bench_unknown_policy_errors(capsys):
    assert cli.main(["bench", "--policies", "nope"]) == 2


def test_evolve_requires_key_or_fake(capsys):
    assert cli.main(["evolve"]) == 2


def test_requires_subcommand():
    with pytest.raises(SystemExit):
        cli.main([])


def test_mem_is_no_subcommand(capsys):
    """The memory ledger went with its command (PR 48): argparse names the
    subcommands that are."""
    with pytest.raises(SystemExit) as e:
        cli.main(["mem", "--sample"])
    assert e.value.code == 2
    assert "invalid choice: 'mem'" in capsys.readouterr().err


def test_cli_scale_synthetic(capsys):
    from fks_tpu.cli import main

    rc = main(["scale", "--nodes-count", "16", "--pods-count", "300",
               "--pop", "2", "--seed", "1"])
    assert rc == 0
    import json as _json

    out = _json.loads(capsys.readouterr().out)
    assert out["pods"] == 300 and out["population"] == 2
    assert out["evals_per_sec"] > 0
    # calibrated load: the seed population should actually schedule
    assert out["score_max"] > 0


# ----------------------------------------------------------- round-4 depth:
# the CLI is the reported-evidence surface, so the fast tier drives the
# evolve loop end-to-end (checkpoint -> resume), the virtual-mesh scale
# path, and the --metrics JSONL schema, not just argparse wiring.

import json


@pytest.fixture
def micro_cli(monkeypatch, micro_workload):
    """Route the CLI's workload loading to the shared micro cluster so
    end-to-end command tests stay in the fast tier (full-trace paths are
    exercised by the engine/evolution suites and the slow tier)."""
    monkeypatch.setattr(cli, "_parse_workload",
                        lambda args: ("micro", micro_workload))
    return micro_workload


def test_evolve_end_to_end_with_checkpoint_and_resume(micro_cli, tmp_path,
                                                      capsys):
    ck = tmp_path / "evolve.ck.json"
    out = tmp_path / "champs"
    metrics = tmp_path / "m1.jsonl"
    rc = cli.main(["evolve", "--fake-llm", "--generations", "2",
                   "--engine", "exact", "--checkpoint", str(ck),
                   "--out", str(out), "--metrics", str(metrics)])
    assert rc == 0
    assert ck.exists()
    stdout = capsys.readouterr().out
    assert "best fitness:" in stdout
    saved = list(out.glob("*.json"))
    assert len(saved) >= 2  # top-K + best-policy JSONs

    rows = [json.loads(l) for l in metrics.read_text().splitlines()]
    gens = [r for r in rows if r["kind"] == "generation"]
    assert [g["generation"] for g in gens] == [1, 2]
    for key in ("best_score", "mean_score", "new_candidates", "accepted",
                "rejected_similar", "eval_seconds", "compile_count", "ts"):
        assert key in gens[0], key

    # resume: same checkpoint, deeper horizon -> continues at generation 3
    metrics2 = tmp_path / "m2.jsonl"
    rc = cli.main(["evolve", "--fake-llm", "--generations", "4",
                   "--engine", "exact", "--checkpoint", str(ck),
                   "--metrics", str(metrics2)])
    assert rc == 0
    rows2 = [json.loads(l) for l in metrics2.read_text().splitlines()]
    gens2 = [r["generation"] for r in rows2 if r["kind"] == "generation"]
    assert gens2 and gens2[0] == 3  # not restarted from 1
    assert gens2[-1] == 4


def test_evolve_champion_json_reference_schema(micro_cli, tmp_path, capsys):
    out = tmp_path / "champs"
    rc = cli.main(["evolve", "--fake-llm", "--generations", "1",
                   "--engine", "exact", "--out", str(out)])
    assert rc == 0
    best = [p for p in out.glob("funsearch_*.json")]
    assert best
    doc = json.loads(best[0].read_text())
    for key in ("code", "score", "generation", "timestamp"):  # ref schema
        assert key in doc, key
    assert "priority_function" in doc["code"]
    assert f"score{doc['score']:.4f}" in best[0].name


def test_scale_runs_sharded_over_virtual_mesh(tmp_path, capsys):
    metrics = tmp_path / "scale.jsonl"
    rc = cli.main(["scale", "--nodes-count", "8", "--pods-count", "80",
                   "--pop", "2", "--seed", "1", "--metrics", str(metrics)])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["mode"] == "sharded over 8 devices"  # conftest's virtual mesh
    assert out["evals_per_sec"] > 0
    rows = [json.loads(l) for l in metrics.read_text().splitlines()]
    assert rows and rows[-1]["kind"] == "scale"
    assert rows[-1]["pods"] == 80


def test_devices_without_cpu_takes_real_devices_or_fails(capsys):
    """``--devices N`` without ``--cpu`` meshes the first N devices the
    backend really has (here: of conftest's eight) and refuses when fewer
    exist — it never quietly runs on one."""
    argv = ["scale", "--nodes-count", "8", "--pods-count", "16", "--pop",
            "2", "--seed", "1", "--engine", "flat"]
    assert cli.main(argv + ["--devices", "2"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["mode"] == "sharded over 2 devices"
    with pytest.raises(SystemExit, match="only 8 cpu device"):
        cli.main(argv + ["--devices", "64"])
    with pytest.raises(SystemExit, match="only 8 cpu device"):
        cli.main(["serve", "--devices", "64", "--selftest", "1"])


def test_scale_code_pop_reports_code_tier(capsys):
    rc = cli.main(["scale", "--nodes-count", "8", "--pods-count", "16",
                   "--pop", "2", "--seed", "1", "--engine", "flat",
                   "--code-pop", "2"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["mode"] == "sharded over 8 devices"
    assert out["code_population"] == 2
    assert out["code_evals_per_sec"] > 0
    assert out["code_engine"] == "flat"


def test_simulate_metrics_schema(micro_cli, tmp_path, capsys):
    metrics = tmp_path / "sim.jsonl"
    rc = cli.main(["simulate", "--policy", "best_fit",
                   "--metrics", str(metrics)])
    assert rc == 0
    row = json.loads(metrics.read_text().splitlines()[-1])
    assert row["kind"] == "simulate" and row["policy"] == "best_fit"
    # the reference-compatible result schema (utils.result_record)
    for key in ("policy_score", "avg_cpu_utilization",
                "avg_memory_utilization", "avg_gpu_count_utilization",
                "avg_gpu_memory_utilization", "gpu_fragmentation_score",
                "num_snapshots", "scheduled_pods", "failed", "truncated"):
        assert key in row, key


def test_metrics_bad_path_fails_fast(micro_cli, tmp_path):
    # missing parent dirs are created; a genuinely unopenable path (a
    # directory) must fail up front, before any simulation work
    with pytest.raises(OSError):
        cli.main(["simulate", "--policy", "best_fit",
                  "--metrics", str(tmp_path)])


def test_evolve_run_dir_then_report_smoke(micro_cli, tmp_path, capsys):
    """Tier-1 smoke (ISSUE 2 satellite): evolve --run-dir writes a valid
    flight-recorder directory, every JSONL line parses against the schema
    helper, and `cli report` renders the summary from the files alone."""
    run_dir = tmp_path / "run"
    rc = cli.main(["evolve", "--fake-llm", "--generations", "2",
                   "--engine", "exact", "--run-dir", str(run_dir)])
    assert rc == 0
    capsys.readouterr()

    # layout + line-by-line schema via the reusable tools/ helper
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import check_jsonl_schema as cjs
    finally:
        sys.path.pop(0)
    counts = cjs.check_run_dir(str(run_dir))
    assert counts["metrics.jsonl"] >= 2  # one ledger row per generation
    assert counts["events.jsonl"] >= 1
    assert counts["heartbeat"] == 1

    meta = json.loads((run_dir / "meta.json").read_text())
    assert meta["command"] == "evolve"
    assert meta["status"] == "ok"
    assert "best_score" in meta
    gens = [json.loads(l) for l
            in (run_dir / "metrics.jsonl").read_text().splitlines()
            if json.loads(l)["kind"] == "generation"]
    assert [g["generation"] for g in gens] == [1, 2]
    for key in ("median_score", "p10_score", "sandbox_failed",
                "transpile_failed", "rescore_fallbacks", "llm_seconds",
                "programs_compiled", "vm_segments"):
        assert key in gens[0], key
    kinds = {json.loads(l)["kind"] for l
             in (run_dir / "events.jsonl").read_text().splitlines()}
    # evolve spans now run under a generation trace ctx -> trace_span
    assert "trace_span" in kinds and "device" in kinds
    assert "compile" in kinds  # jax.monitoring listener captured compiles

    rc = cli.main(["report", str(run_dir)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "generations: 2" in out
    assert "status ok" in out
    assert "spans (by path" in out
    assert "compile events:" in out
    assert "fitness best" in out

    # a non-run directory errors cleanly, not with a traceback
    assert cli.main(["report", str(tmp_path / "nope")]) == 2


def test_scale_run_dir_records_mesh(tmp_path, capsys):
    run_dir = tmp_path / "run"
    rc = cli.main(["scale", "--nodes-count", "8", "--pods-count", "80",
                   "--pop", "5", "--seed", "1", "--run-dir", str(run_dir)])
    assert rc == 0
    capsys.readouterr()
    events = [json.loads(l) for l
              in (run_dir / "events.jsonl").read_text().splitlines()]
    mesh = [e for e in events if e["kind"] == "mesh"]
    assert mesh and mesh[0]["shards"] == 8
    # pop 5 on 8 shards pads 3 lanes
    assert mesh[0]["pad_lanes"] == 3
    assert mesh[0]["pad_waste_fraction"] == pytest.approx(3 / 8)
    rows = [json.loads(l) for l
            in (run_dir / "metrics.jsonl").read_text().splitlines()]
    assert rows[-1]["kind"] == "scale" and rows[-1]["evals_per_sec"] > 0
    rc = cli.main(["report", str(run_dir)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "mesh: 8 shards" in out and "pad waste 37.5%" in out


def test_divergence_bound_reads_latest_row(tmp_path):
    p = tmp_path / "audit.jsonl"
    rows = [{"trace": "t.csv", "max_abs_d": 0.01},
            {"trace": "casc.csv", "max_abs_d": 0.43, "max_drift": 0.008,
             "flat_cascades": 1},
            {"trace": "t.csv", "max_abs_d": 0.02},  # latest t.csv row wins
            {"trace": "t.csv", "error": "boom"}]  # error rows are skipped
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    # pre-cascade-era rows (no max_drift) fall back to max_abs_d
    assert cli._divergence_bound("t.csv", str(p)) == (0.02, 0)
    # cascade rows report arithmetic drift + the cascade count separately
    assert cli._divergence_bound("casc.csv", str(p)) == (0.008, 1)
    assert cli._divergence_bound("missing.csv", str(p)) is None
    assert cli._divergence_bound("t.csv", str(tmp_path / "nope")) is None
