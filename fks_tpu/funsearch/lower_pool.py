"""A generation's sources checked, keyed, lowered and packed side by side,
on the host's idle cores.

Everything ``backend._evaluate`` does once per SOURCE is pure Python over
that source alone: the static pre-flight (``analysis.preflight_check``,
4 ms) and the canonical key (``transpiler.canonical_key``, 1 ms) over its
AST, the lowering (``vm.lower_ops``: one ``jax.make_jaxpr`` trace of its
body, 30-45 ms that hold the GIL since PR 50, some 100 when the pool was
built, then ``vm.simplify_ops``) and the packing of what is kept into the
program's words (``vm.pack_words``). A generation's sources are
independent. Threads cannot split that work, processes can: what a source
leaves behind is plain Python and NumPy (a verdict, a key, a fingerprint,
``simplify_ops``' op tuples, the words) and pickles in microseconds. So
the batched VM tier hands a generation to ONE process-wide pool of worker
processes, one source a task (`lower_source`, the whole of it), and keeps
what is per GENERATION for itself: the dedup on what comes back, the lane
order, the stack and the one upload, which belongs to the process that
holds the chip.

The workers are forked by a nursery: a fresh interpreter (``subprocess``,
never a fork of THIS process, which holds libtpu and XLA's threads) with
``JAX_PLATFORMS=cpu`` in its environment, which imports jax, the
lowering and ``fks_tpu.analysis`` once for all of them and forks before
any thread, backend or array exists. So a worker never touches the chip;
every task carries the caller's ``jax_enable_x64``, so it traces and packs
in the caller's arithmetic, and the checks the caller's evaluator asks
for. It talks over one socket pair and lives as long as the parent's end
of it: a parent that exits, however it exits, closes that end and the
worker's next read ends it. It runs `lower_source`, the function the
in-process path runs.

The pool adapts and never makes a generation wait for it: fewer than two
usable cores (``os.sched_getaffinity`` less one for the parent), a single
source, workers that are still starting (2.5 s), a pool that did not start
or lost a worker, and the sources no worker is there for are checked and
lowered here, by the same function, one after another; a broken pool is
dropped, counted (`drops`) and started again by the next generation.
There is no option: the size is ``min(sources, usable cores,
MAX_WORKERS)``.
"""
from __future__ import annotations

import atexit
import collections
import multiprocessing.connection as mpc
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax

from fks_tpu.funsearch import template, transpiler, vm
from fks_tpu.utils import get_logger

#: the most workers a process keeps, whatever its cores and generations
MAX_WORKERS = 16
#: workers are ready after the nursery's imports and one lowering each
#: (2.5 s on idle cores); a pool still starting after this long is dropped
START_TIMEOUT_S = 120.0
_WARM_POLICY = "best_fit"
#: `lower_all`'s counters for a generation no worker touched
NOT_POOLED = {"pooled": 0, "workers": 0}


class Source(NamedTuple):
    """A source with the checks its evaluator asks for
    (``CodeEvaluator``'s ``preflight`` / ``fp_dedup``): what a task
    carries beside the shape and the precision. A bare string is a source
    that asks for none."""

    code: str
    preflight: bool = False  # reject on the static pre-flight's verdict
    fp_dedup: bool = False  # bring its fingerprint home


def _asked(source: Union[str, Source]) -> Source:
    return Source(source) if isinstance(source, str) else source


class Rejection(NamedTuple):
    """Why a source never reaches the lowering: the static pre-flight's
    verdict (``analysis.REJECT_TAXONOMY``), or, with ``taxonomy`` None,
    the ``SyntaxError`` of ``transpiler.canonical_key``."""

    taxonomy: Optional[str]
    reason: str


class Lowered(NamedTuple):
    """What one source leaves behind, as plain Python and NumPy, and where
    and when: the check's verdict, key and fingerprint, the lowering's ops
    and the program's words. Stamps on ``time.perf_counter``, which is
    ``CLOCK_MONOTONIC`` on Linux, one clock for every process of the host
    (`clock_misfit` checks that it was)."""

    kept: Optional[tuple]  # simplify_ops' (ops, consts, out_reg)
    ops_lowered: int  # ops the lowering emitted, before simplify_ops
    traces: int  # times the policy's body ran (transpiler.body_runs)
    error: Optional[Exception]  # what `vm.compile_policy` would have raised
    pid: int = 0  # the process that checked and lowered it
    t0: float = 0.0  # `lower_source` entered: the check begins
    t_checked: float = 0.0  # `check_source` was through: the lowering begins
    t_traced: float = 0.0  # ``vm.lower_ops`` was through (or raised)
    t1: float = 0.0  # ``vm.simplify_ops`` was through (the words follow)
    eqns: int = 0  # equations of the traced jaxpr (vm.eqns_traced)
    # whole column chains simplify_ops folded into their grid
    # (vm.chains_folded): 5 for a ledger champion
    chains_folded: int = 0
    # the parent's own stamps around a worker's task (before the send,
    # after the receive); None for a source lowered in process
    sent: Optional[float] = None
    received: Optional[float] = None
    key: Optional[str] = None  # transpiler.canonical_key; None: rejected
    fingerprint: Optional[str] = None  # the pre-flight's, where it ran
    work: Optional[int] = None  # its static work bound at g GPUs a node
    rejection: Optional[Rejection] = None  # the source stops here
    # vm.pack_words of ``kept``: a VMProgram of NumPy leaves in the
    # caller's dtypes, at the program's own capacity bucket
    words: Optional[vm.VMProgram] = None


def check_source(source: Union[str, Source], g: int) -> Lowered:
    """The per-source half of the evaluator's pre-flight, where the
    `Source` asks for it (either of its flags runs
    ``analysis.preflight_check``; only ``preflight`` rejects on it), then
    ``transpiler.canonical_key``. A `Lowered` that holds the check alone:
    `lower_source` goes on from it unless it holds a ``rejection``."""
    # lazy: fks_tpu.analysis pulls funsearch tables, and funsearch/__init__
    # imports this module first (the nursery imports it before it forks)
    from fks_tpu import analysis

    code, preflight, fp_dedup = _asked(source)
    key = fingerprint = work = rejection = None
    t0 = time.perf_counter()
    if preflight or fp_dedup:
        rep = analysis.preflight_check(code)
        if preflight and not rep.ok:
            rejection = Rejection(rep.taxonomy, rep.reason)
        elif rep.ok:
            fingerprint = rep.fingerprint
            work = None if rep.cost is None else rep.cost.work(g)
    if rejection is None:
        try:
            key = transpiler.canonical_key(code)
        except SyntaxError as e:
            rejection = Rejection(None, str(e))
    t = time.perf_counter()
    return Lowered(None, 0, 0, None, pid=os.getpid(), t0=t0, t_checked=t,
                   t_traced=t, t1=t, key=key, fingerprint=fingerprint,
                   work=work, rejection=rejection)


def lower_source(source: Union[str, Source], n: int, g: int) -> Lowered:
    """Everything that is done once per source, in the process that is
    called (a worker of the pool, or the evaluator's own): `check_source`,
    returning at once for a statically doomed source or a syntax error,
    which never reach the lowering; ``vm.lower_ops`` then
    ``vm.simplify_ops`` at padded shapes (n, g); then ``vm.pack_words`` of
    what is kept, all in the ambient ``jax_enable_x64``. Nothing here
    touches a device. The lowering's exception is returned, not raised,
    rebuilt from its message so that it always pickles: ``VMUnsupported``
    and ``TranspileError`` keep their class, anything else (candidate
    code is untrusted) becomes a ``RuntimeError``."""
    source = _asked(source)
    low = check_source(source, g)
    if low.rejection is not None:
        return low
    runs0, eqns0 = transpiler.body_runs(), vm.eqns_traced()
    folds0 = vm.chains_folded()
    kept, lowered, error, t_traced = None, 0, None, None
    try:
        ops, consts, out_reg = vm.lower_ops(source.code, n, g)
        t_traced = time.perf_counter()
        kept, lowered = vm.simplify_ops(ops, consts, out_reg, g), len(ops)
    except (vm.VMUnsupported, transpiler.TranspileError) as e:
        error = type(e)(str(e))
    except Exception as e:  # noqa: BLE001 — untrusted code
        error = RuntimeError(str(e))
    t1 = time.perf_counter()
    return low._replace(
        kept=kept, ops_lowered=lowered, error=error,
        traces=transpiler.body_runs() - runs0,
        t_traced=t1 if t_traced is None else t_traced, t1=t1,
        eqns=vm.eqns_traced() - eqns0,
        chains_folded=vm.chains_folded() - folds0,
        words=None if kept is None else vm.pack_words(*kept))


def clock_misfit(lowered: Sequence[Lowered]) -> bool:
    """True when a worker's stamps do not lie inside the parent's own
    send and receive stamps for that task: the two processes do not read
    one clock (a container boundary, another clock source), and an
    interval from the worker's would be wrong on the parent's."""
    return any(not low.sent <= low.t0 <= low.t1 <= low.received
               for low in lowered if low.sent is not None)


def serve(fd: int, n: int, g: int, x64: bool) -> None:
    """A worker's whole life: warm up (one seed policy checked and
    lowered at the shape and precision of the evaluator that started the
    pool), say so, then run `lower_source` on what arrives on ``fd`` until
    the parent's end closes."""
    conn = mpc.Connection(fd)
    jax.config.update("jax_enable_x64", x64)
    lower_source(Source(template.seed_policies()[_WARM_POLICY], True, True),
                 n, g)
    conn.send({"pid": os.getpid(), "backend": jax.default_backend(),
               "x64": bool(jax.config.jax_enable_x64)})
    while True:
        try:
            x64, task = conn.recv()
        except EOFError:
            return
        if x64 != jax.config.jax_enable_x64:
            jax.config.update("jax_enable_x64", x64)
        conn.send(lower_source(*task))


def nursery(fds: Sequence[int], n: int, g: int, x64: bool) -> None:
    """The workers' parent: a fresh interpreter that has imported this
    module (jax and the lowering with it: 2 s, paid ONCE a pool) and the
    pre-flight, and holds no thread, no backend and no array, so it may
    fork. One worker a descriptor; then it reaps them and ends with the
    last."""
    from fks_tpu import analysis  # noqa: F401 — once, for every worker
    for fd in fds:
        if os.fork() == 0:
            status = 1
            try:
                for other in fds:
                    if other != fd:
                        os.close(other)
                serve(fd, n, g, x64)
                status = 0
            finally:
                os._exit(status)
    for fd in fds:
        os.close(fd)
    while True:
        try:
            os.wait()
        except ChildProcessError:
            return


class _Pool:
    """``size`` workers behind one nursery, in a process group of their
    own (the terminal's Ctrl-C is the parent's). `lower` never waits for a
    worker that is still starting."""

    def __init__(self, size: int, n: int, g: int):
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        path = os.pathsep.join(
            p for p in (root, os.environ.get("PYTHONPATH")) if p)
        # no BLAS pool either: the nursery forks, and nothing here is BLAS
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=path,
                   OPENBLAS_NUM_THREADS="1")
        pairs = [socket.socketpair() for _ in range(size)]
        theirs = [t.fileno() for _, t in pairs]
        try:
            self.proc = subprocess.Popen(
                [sys.executable, "-c",
                 "from fks_tpu.funsearch.lower_pool import nursery; "
                 f"nursery({theirs}, {n}, {g}, "
                 f"{bool(jax.config.jax_enable_x64)})"],
                pass_fds=theirs, env=env, process_group=0,
                stdin=subprocess.DEVNULL, stdout=subprocess.DEVNULL)
        finally:
            for _, t in pairs:
                t.close()
        self.born = time.perf_counter()
        self.starting = [mpc.Connection(o.detach()) for o, _ in pairs]
        self.ready: Dict[mpc.Connection, dict] = {}  # -> its ready message

    def arrivals(self) -> List[mpc.Connection]:
        """The workers that finished their start since the last look."""
        if self.starting and (time.perf_counter() - self.born
                              > START_TIMEOUT_S):
            raise TimeoutError(
                f"workers not ready in {START_TIMEOUT_S:.0f} s")
        came = mpc.wait(self.starting, 0) if self.starting else []
        for conn in came:
            self.ready[conn] = conn.recv()
            self.starting.remove(conn)
        return came

    def lower(self, tasks: Sequence[tuple],
              x64: bool) -> Tuple[List[Lowered], int, int]:
        """One task (`lower_source`'s arguments) a free worker until none
        is left: the results in the tasks' order, how many of them a
        worker ran and how many workers ran one. While workers are still
        starting, a task no ready worker is free for is run HERE instead
        of waited with (a process's first generation is lowered as before
        the pool, and costs what it cost)."""
        out: List[Optional[Lowered]] = [None] * len(tasks)
        todo = collections.deque(enumerate(tasks))
        idle = list(self.ready)
        busy: Dict[mpc.Connection, Tuple[int, float]] = {}  # -> task, sent
        used, pooled = set(), 0
        while todo or busy:
            idle += self.arrivals()
            while todo and idle:
                i, task = todo.popleft()
                conn = idle.pop()
                busy[conn] = i, time.perf_counter()
                conn.send((x64, task))
            if todo and self.starting:
                i, task = todo.popleft()
                out[i] = lower_source(*task)
                continue
            for conn in mpc.wait(list(busy)):
                i, sent = busy.pop(conn)
                out[i] = conn.recv()._replace(
                    sent=sent, received=time.perf_counter())
                pooled += 1
                used.add(conn)
                idle.append(conn)
        return out, pooled, len(used)

    def kill(self) -> None:
        """End nursery and workers at once (they keep nothing)."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    def close(self) -> None:
        for conn in [*self.starting, *self.ready]:
            conn.close()
        self.kill()
        self.proc.wait()


_lock = threading.Lock()  # one generation holds the pool at a time
_pool: Optional[_Pool] = None
_drops = 0


def usable_cores() -> int:
    """Cores the process may run on, less the parent's own."""
    return len(os.sched_getaffinity(0)) - 1


def _size(sources: Optional[int]) -> int:
    """Workers for a generation of ``sources`` (None: not known yet)."""
    return min(usable_cores(), MAX_WORKERS,
               *(() if sources is None else (sources,)))


def drops() -> int:
    """Pools dropped by this process: ones that did not start or broke."""
    return _drops


def workers() -> List[dict]:
    """The ready messages (pid, backend, x64) of the live pool's workers
    that have finished their start."""
    with _lock:
        if _pool is None:
            return []
        _pool.arrivals()
        return list(_pool.ready.values())


def _drop(why: BaseException) -> None:
    global _pool, _drops
    get_logger("fks_tpu.funsearch.lower_pool").warning(
        "lowering pool dropped (%s: %s); this generation is lowered in "
        "process", type(why).__name__, why)
    if _pool is not None:
        _pool.close()
    _pool, _drops = None, _drops + 1


def _started(sources: Optional[int], n: int, g: int) -> Optional[_Pool]:
    """The process's pool, spawned here if it has none and the cores for
    one; the caller holds `_lock`."""
    global _pool
    if _pool is None and _size(sources) >= 2:
        try:
            _pool = _Pool(_size(sources), n, g)
        except OSError as e:
            _drop(e)
    return _pool


def start(n: int, g: int) -> None:
    """Spawn the pool and return at once. `CodeEvaluator` calls it before
    any generation is known, so the cores alone size it."""
    with _lock:
        _started(None, n, g)


def lower_all(codes: Sequence[Union[str, Source]], n: int,
              g: int) -> Tuple[List[Lowered], Dict[str, int]]:
    """`lower_source` of every source, in order, and the stage's counters:
    ``pooled`` (sources a worker ran) and ``workers`` (that ran at least
    one). Side by side in the pool's workers where the process has them,
    else here, one after another."""
    tasks = [(c, n, g) for c in codes]
    if _size(len(codes)) >= 2:
        with _lock:
            pool = _started(len(codes), n, g)
            try:
                if pool is not None:
                    out, pooled, used = pool.lower(
                        tasks, bool(jax.config.jax_enable_x64))
                    return out, {"pooled": pooled, "workers": used}
            except Exception as e:  # noqa: BLE001 — never the generation's
                _drop(e)
    return [lower_source(*task) for task in tasks], NOT_POOLED


@atexit.register
def _shutdown() -> None:
    """Interpreter exit: kill, do not wait (a parent that dies without
    running this closes its sockets all the same, which ends the
    workers)."""
    if _pool is not None:
        _pool.kill()
