"""Promotion-pipeline tests (fks_tpu.pipeline).

The ISSUE-12 acceptance criteria, as tests:

- the promotion.jsonl state machine: legal/illegal transitions, reload
  round-trip, torn-tail tolerance (kill -9 mid-append) + self-repair;
- gates: a fitness loser is rejected before any device work, a corrupt
  champion degrades to REJECTED at load, an injected p99 regression is
  rejected at shadow — serve keeps answering on the incumbent;
- the hot swap: promotion flips the engine atomically with ZERO
  recompiles on the post-swap warm path (the ladder compiled off the
  request path);
- kill -9 right after each state record lands: a fresh controller +
  service resumes to a consistent state from the log alone;
- probation: post-promotion SLO burn rolls back automatically (and the
  recorded run dir passes the schema checker); a quiet probation window
  releases with PROBATION_PASSED;
- the ``serve --follow-ledger`` poll thread promotes a dropped champion
  end to end;
- the slow tier runs the whole deterministic drill matrix.
"""
import dataclasses
import json
import os
import sys
import time

import pytest

from fks_tpu.data.synthetic import synthetic_workload
from fks_tpu.funsearch import template
from fks_tpu.obs import CompileWatcher, FlightRecorder, recording
from fks_tpu.pipeline import (
    FaultPlan, KillSwitch, PromotionConfig, PromotionController,
    PromotionLog, attempt_id, follow_ledger, write_champion,
    write_corrupt_champion,
)
from fks_tpu.serve import (
    ChampionSpec, ServeEngine, ServeService, ShapeEnvelope, latest_champion,
    load_champion,
)
from fks_tpu.serve.accounting import SLOConfig

BETTER_LOGIC = ("score = 1000 + (node.cpu_milli_left - pod.cpu_milli) "
                "/ max(1, node.cpu_milli_total)")


class RecStub:
    """Recorder double: keeps every event/metric for assertions."""

    def __init__(self):
        self.events = []
        self.metrics = []

    def event(self, kind, **fields):
        self.events.append({"kind": kind, **fields})

    def metric(self, kind, record=None, **fields):
        self.metrics.append({"kind": kind, **fields})


class Stack:
    """Shared warm serving stack: one incumbent, engines cached per
    champion code so the module pays each XLA compile once."""

    def __init__(self):
        self.wl = synthetic_workload(8, 16, seed=0)
        self.envelope = ShapeEnvelope(max_pods=8, min_pod_bucket=8,
                                      max_batch=2)
        self._cache = {}
        self.incumbent = self.factory(ChampionSpec(
            code=template.fill_template("score = 1000"), score=0.4,
            source="<test-seed>"))

    def factory(self, champ):
        if champ.code not in self._cache:
            eng = ServeEngine(champ, self.wl, envelope=self.envelope)
            eng.warmup()
            self._cache[champ.code] = eng
        return self._cache[champ.code]


@pytest.fixture(scope="module")
def stack():
    return Stack()


def _service(stack):
    return ServeService(stack.incumbent, max_wait_s=0.002)


def _traffic(service, n=3, pods=3):
    base = service.engine.base_pods
    futs = [service.submit(
        {"pods": [dict(base[(i + j) % len(base)]) for j in range(pods)]})
        for i in range(n)]
    return [f.result(timeout=300) for f in futs]


def _ctrl(stack, service, tmp, *, faults=None, recorder=None, **over):
    cfg = PromotionConfig(shadow_queries=2, **over)
    return PromotionController(
        service, stack.wl, ledger_dir=str(tmp),
        log_path=os.path.join(str(tmp), "promotion.jsonl"), config=cfg,
        recorder=recorder, faults=faults, engine_factory=stack.factory)


def _better(tmp, score=0.9):
    return write_champion(str(tmp), template.fill_template(BETTER_LOGIC),
                          score)


# -------------------------------------------------------- promotion log


def test_promotion_log_lifecycle(tmp_path):
    log = PromotionLog(tmp_path / "promotion.jsonl")
    log.append("a1", "PENDING", champion="c.json")
    log.append("a1", "SHADOW", champion="c.json")
    log.append("a1", "PROMOTED", champion="c.json")
    assert log.state_of("a1") == "PROMOTED"
    assert log.active()["attempt"] == "a1"
    with pytest.raises(ValueError):
        log.append("a1", "SHADOW")       # PROMOTED only ever rolls back
    with pytest.raises(ValueError):
        log.append("a2", "SHADOW")       # new attempts start at PENDING
    with pytest.raises(ValueError):
        log.append("a1", "LAUNCHED")     # unknown state
    log.append("a1", "ROLLED_BACK", champion="c.json")
    assert log.active() is None
    with pytest.raises(ValueError):
        log.append("a1", "PENDING")      # terminal states are closed
    # reload round-trips the latest-state map
    log2 = PromotionLog(log.path)
    assert log2.states() == {"a1": "ROLLED_BACK"}
    assert log2.skipped_lines == 0


def test_promotion_log_torn_tail_skipped_and_repaired(tmp_path):
    path = tmp_path / "promotion.jsonl"
    log = PromotionLog(path)
    log.append("a1", "PENDING")
    log.append("a1", "SHADOW")
    # a kill -9 mid-append leaves a torn trailing line with no newline
    with open(path, "a") as f:
        f.write('{"ts": 1, "attempt": "a1", "state": "PROMO')
    log2 = PromotionLog(path)
    assert log2.skipped_lines == 1
    assert log2.state_of("a1") == "SHADOW"  # the torn record never happened
    assert log2.interrupted() == ["a1"]
    # the next append repairs the missing newline; the file stays JSONL
    log2.append("a1", "PROMOTED")
    log3 = PromotionLog(path)
    assert log3.skipped_lines == 1
    assert log3.state_of("a1") == "PROMOTED"
    assert log3.active() is not None


def test_attempt_id_content_addressed(tmp_path):
    a = write_champion(str(tmp_path), "code-a", 0.5, name="a")
    b = write_champion(str(tmp_path), "code-b", 0.5, name="b")
    assert attempt_id(a) == attempt_id(a)
    assert attempt_id(a) != attempt_id(b)  # different bytes, new attempt


# ------------------------------------------------------ gates + rejects


def test_fitness_gate_rejects_before_any_device_work(stack, tmp_path):
    service = _service(stack)
    calls = []

    def factory(champ):
        calls.append(champ)
        return stack.factory(champ)

    try:
        _better(tmp_path, score=0.1)  # worse than the incumbent's 0.4
        ctrl = PromotionController(
            service, stack.wl, ledger_dir=str(tmp_path),
            config=PromotionConfig(shadow_queries=2),
            engine_factory=factory)
        out = ctrl.poll_once()
        assert out["action"] == "rejected"
        assert "fitness" in out["reason"]
        assert not calls  # a fitness loser never costs a ladder build
        assert ctrl.log.state_of(out["attempt"]) == "REJECTED"
        assert ctrl.poll_once()["action"] == "idle"  # never retried
    finally:
        service.close()


def test_load_champion_validates_fields(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text('{"code": "def f(): pass", ')  # torn mid-write
    with pytest.raises(ValueError, match="JSON"):
        load_champion(str(p))
    p.write_text(json.dumps({"code": "", "score": 1.0}))
    with pytest.raises(ValueError, match="code"):
        load_champion(str(p))
    p.write_text(json.dumps({"code": "def f(): pass", "score": "wat"}))
    with pytest.raises(ValueError, match="score"):
        load_champion(str(p))
    p.write_text(json.dumps({"code": "def f(): pass", "score": "Infinity"}))
    with pytest.raises(ValueError, match="non-finite"):
        load_champion(str(p))


def test_corrupt_champion_skipped_with_warning(tmp_path):
    rec = RecStub()
    write_corrupt_champion(str(tmp_path))
    # the torn file (best score in the dir) must not hide the ledger
    assert latest_champion(str(tmp_path), recorder=rec) is None
    alerts = [e for e in rec.events if e["kind"] == "alert"]
    assert alerts and alerts[0]["source"] == "champion_ledger"
    good = write_champion(str(tmp_path), "def f(): pass", 0.7, name="good")
    assert latest_champion(str(tmp_path), recorder=rec) == good


def test_corrupt_champion_rejected_serving_survives(stack, tmp_path):
    service = _service(stack)
    try:
        corrupt = write_corrupt_champion(str(tmp_path))
        ctrl = _ctrl(stack, service, tmp_path)
        out = ctrl.poll_once(corrupt)
        assert out["action"] == "rejected"
        assert "load_failed" in out["reason"]
        assert len(_traffic(service, 2)) == 2
    finally:
        service.close()


def test_p99_regression_rejected_at_shadow(stack, tmp_path):
    service = _service(stack)
    try:
        _traffic(service, 3)
        _better(tmp_path)
        ctrl = _ctrl(stack, service, tmp_path,
                     faults=FaultPlan(shadow_latency_ms=400.0),
                     max_p99_regression=1.5, slo=SLOConfig(p99_ms=50.0))
        out = ctrl.poll_once()
        assert out["action"] == "rejected"
        assert "latency" in out["reason"] or "slo" in out["reason"]
        assert service.engine is stack.incumbent
        assert service.swaps == 0
    finally:
        service.close()


# --------------------------------------------------------- the hot swap


def test_promotion_hot_swap_zero_recompiles(stack, tmp_path):
    service = _service(stack)
    try:
        _traffic(service, 3)
        _better(tmp_path)
        ctrl = _ctrl(stack, service, tmp_path)
        out = ctrl.poll_once()
        assert out["action"] == "promoted"
        assert service.swaps == 1
        assert service.engine.champion.score == 0.9
        watcher = CompileWatcher().install()
        try:
            answers = _traffic(service, 4)
            assert len(answers) == 4
            # the contract the swap exists for: the promoted ladder was
            # compiled off the request path, so warm traffic compiles 0
            assert watcher.backend_compile_count == 0
        finally:
            watcher.uninstall()
        assert ctrl.poll_once()["action"] == "idle"
    finally:
        service.close()


@pytest.mark.parametrize("state", ["PENDING", "SHADOW", "PROMOTED"])
def test_kill_and_recover(stack, tmp_path, state):
    service = _service(stack)
    try:
        cand = _better(tmp_path)
        ctrl = _ctrl(stack, service, tmp_path,
                     faults=FaultPlan(kill_after_state=state))
        with pytest.raises(KillSwitch):
            ctrl.poll_once()
        # the crashed controller never took serving down
        assert len(_traffic(service, 2)) == 2
        # a restarted process: fresh service + controller, same log
        service2 = _service(stack)
        try:
            ctrl2 = _ctrl(stack, service2, tmp_path)
            rec = ctrl2.recover()
            out = ctrl2.poll_once()
            if state == "PROMOTED":
                # the log committed before the flip: restart resolves to
                # the candidate with nothing left to replay
                assert rec["active"] is not None
                assert ctrl2.active_champion() == cand
                assert out["action"] == "idle"
            else:
                assert rec["interrupted"]
                assert out["action"] == "promoted"
                assert service2.engine.champion.score == 0.9
        finally:
            service2.close()
    finally:
        service.close()


# ------------------------------------------------------------ probation


def test_rollback_on_burn_and_run_dir_schema(stack, tmp_path):
    run_dir = tmp_path / "run"
    ledger = tmp_path / "ledger"
    rec = FlightRecorder(str(run_dir))
    service = ServeService(stack.incumbent, max_wait_s=0.002, recorder=rec)
    try:
        with recording(rec):
            _traffic(service, 2)
            _better(ledger)
            ctrl = PromotionController(
                service, stack.wl, ledger_dir=str(ledger),
                config=PromotionConfig(shadow_queries=2,
                                       probation_requests=32),
                recorder=rec, engine_factory=stack.factory)
            assert ctrl.poll_once()["action"] == "promoted"
            # production degrades post-swap: every request now misses the
            # (retroactively impossible) p99 target
            ctrl.cfg = dataclasses.replace(ctrl.cfg,
                                           slo=SLOConfig(p99_ms=1e-6))
            _traffic(service, 3)
            out = ctrl.check_probation()
            assert out is not None and out["action"] == "rolled_back"
            assert service.engine is stack.incumbent
            assert ctrl.log.state_of(out["attempt"]) == "ROLLED_BACK"
            assert ctrl.poll_once()["action"] == "idle"
    finally:
        service.close()
    # everything the pipeline recorded parses against the schema tool
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import check_jsonl_schema as cjs
    finally:
        sys.path.pop(0)
    counts = cjs.check_run_dir(str(run_dir))
    assert counts["metrics.jsonl"] > 0
    events = [json.loads(ln) for ln in
              (run_dir / "events.jsonl").read_text().splitlines()]
    assert any(e["kind"] == "rollback" for e in events)
    states = [m.get("state") for m in
              (json.loads(ln) for ln in
               (run_dir / "metrics.jsonl").read_text().splitlines())
              if m.get("kind") == "promotion_event"]
    assert "PROMOTED" in states and "ROLLED_BACK" in states


def test_probation_release(stack, tmp_path):
    service = _service(stack)
    try:
        _traffic(service, 2)
        _better(tmp_path)
        ctrl = _ctrl(stack, service, tmp_path, probation_requests=2,
                     slo=SLOConfig(p99_ms=1e9))
        assert ctrl.poll_once()["action"] == "promoted"
        _traffic(service, 3)
        out = ctrl.check_probation()
        assert out is not None and out["action"] == "probation_passed"
        assert ctrl.check_probation() is None  # released exactly once
    finally:
        service.close()


# -------------------------------------------------- follow-ledger + CLI


def test_follow_ledger_thread_promotes(stack, tmp_path):
    service = _service(stack)
    try:
        ctrl = _ctrl(stack, service, tmp_path)
        stop, thread = follow_ledger(ctrl, interval=0.05)
        try:
            _better(tmp_path)
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline and service.swaps == 0:
                time.sleep(0.05)
            assert service.swaps == 1
            assert service.engine.champion.score == 0.9
        finally:
            stop.set()
            thread.join(timeout=30)
    finally:
        service.close()


def test_cli_pipeline_status(tmp_path, capsys):
    from fks_tpu import cli

    log = PromotionLog(tmp_path / "promotion.jsonl")
    log.append("abc", "PENDING", champion="c.json")
    log.append("abc", "SHADOW", champion="c.json")
    rc = cli.main(["pipeline", "--cpu", "--ledger-dir", str(tmp_path)])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["attempts"] == {"abc": "SHADOW"}
    assert out["interrupted"] == ["abc"]
    assert out["active"] is None
    assert out["skipped_lines"] == 0


# ----------------------------------------------------- the drill matrix


@pytest.mark.slow
def test_full_drill_matrix():
    from fks_tpu.pipeline import run_drills

    results = run_drills(log=lambda _m: None)
    assert results, "empty drill matrix"
    failed = [r for r in results if not r["ok"]]
    assert not failed, failed
