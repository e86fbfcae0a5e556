"""A generation's sources lowered side by side, on the host's idle cores.

Lowering a candidate (``vm.lower_ops``: one ``jax.make_jaxpr`` trace of
its body, 30-45 ms of pure Python that holds the GIL since PR 50, some 100
when the pool was built) is the larger half of ``backend._evaluate``'s
transpile stage, and a generation's sources are
independent. Threads cannot split that work, processes can: what a source
leaves behind is plain Python (``simplify_ops``' op tuples, the pool's
constants, a register number) and pickles in microseconds. So the batched
VM tier hands a generation to ONE process-wide pool of worker processes,
one source a task, and packs what comes back (``vm.pack_program``: the
uploads belong to the process that holds the chip).

The workers are forked by a nursery: a fresh interpreter (``subprocess``,
never a fork of THIS process, which holds libtpu and XLA's threads) with
``JAX_PLATFORMS=cpu`` in its environment, which imports jax and the
lowering once for all of them and forks before any thread, backend or
array exists. So a worker never touches the chip; every task carries the
caller's ``jax_enable_x64``, so it traces in the caller's arithmetic. It
talks over one socket pair and lives as long as the parent's end of it: a
parent that exits, however it exits, closes that end and the worker's next
read ends it. It runs `lower_source`, the function the in-process path
runs.

The pool adapts and never makes a generation wait for it: fewer than two
usable cores (``os.sched_getaffinity`` less one for the parent), a single
source, workers that are still starting (2.5 s), a pool that did not start
or lost a worker, and the sources no worker is there for are lowered here,
by the same function; a broken pool is dropped, counted (`drops`) and
started again by the next generation. There is no option: the size is
``min(sources, usable cores, MAX_WORKERS)``.
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
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

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


class Lowered(NamedTuple):
    """What lowering one source leaves behind, as plain Python, and where
    and when it was lowered: stamps on ``time.perf_counter``, which is
    ``CLOCK_MONOTONIC`` on Linux, one clock for every process of the host
    (`clock_misfit` checks that it was)."""

    kept: Optional[tuple]  # simplify_ops' (ops, consts, out_reg)
    ops_lowered: int  # ops the lowering emitted, before simplify_ops
    traces: int  # times the policy's body ran (transpiler.body_runs)
    error: Optional[Exception]  # what `vm.compile_policy` would have raised
    pid: int = 0  # the process that lowered it
    t0: float = 0.0  # `lower_source` entered
    t_traced: float = 0.0  # ``vm.lower_ops`` was through (or raised)
    t1: float = 0.0  # `lower_source` returned
    eqns: int = 0  # equations of the traced jaxpr (vm.eqns_traced)
    # the parent's own stamps around a worker's task (before the send,
    # after the receive); None for a source lowered in process
    sent: Optional[float] = None
    received: Optional[float] = None


def lower_source(code: str, n: int, g: int) -> Lowered:
    """``vm.lower_ops`` then ``vm.simplify_ops`` at padded shapes (n, g),
    in the ambient ``jax_enable_x64``. An exception is returned, not
    raised, rebuilt from its message so that it always pickles:
    ``VMUnsupported`` and ``TranspileError`` keep their class, anything
    else (candidate code is untrusted) becomes a ``RuntimeError``."""
    runs0, eqns0 = transpiler.body_runs(), vm.eqns_traced()
    kept, lowered, error, t_traced = None, 0, None, None
    t0 = time.perf_counter()
    try:
        ops, consts, out_reg = vm.lower_ops(code, n, g)
        t_traced = time.perf_counter()
        kept, lowered = vm.simplify_ops(ops, consts, out_reg), len(ops)
    except (vm.VMUnsupported, transpiler.TranspileError) as e:
        error = type(e)(str(e))
    except Exception as e:  # noqa: BLE001 — untrusted code
        error = RuntimeError(str(e))
    t1 = time.perf_counter()
    return Lowered(kept, lowered, transpiler.body_runs() - runs0, error,
                   os.getpid(), t0, t1 if t_traced is None else t_traced, t1,
                   vm.eqns_traced() - eqns0)


def clock_misfit(lowered: Sequence[Lowered]) -> bool:
    """True when a worker's stamps do not lie inside the parent's own
    send and receive stamps for that task: the two processes do not read
    one clock (a container boundary, another clock source), and an
    interval from the worker's would be wrong on the parent's."""
    return any(not low.sent <= low.t0 <= low.t1 <= low.received
               for low in lowered if low.sent is not None)


def serve(fd: int, n: int, g: int, x64: bool) -> None:
    """A worker's whole life: warm up (one seed policy at the shape and
    precision of the evaluator that started the pool), say so, then lower
    what arrives on ``fd`` until the parent's end closes."""
    conn = mpc.Connection(fd)
    jax.config.update("jax_enable_x64", x64)
    lower_source(template.seed_policies()[_WARM_POLICY], n, g)
    conn.send({"pid": os.getpid(), "backend": jax.default_backend(),
               "x64": bool(jax.config.jax_enable_x64)})
    while True:
        try:
            code, n, g, x64 = conn.recv()
        except EOFError:
            return
        if x64 != jax.config.jax_enable_x64:
            jax.config.update("jax_enable_x64", x64)
        conn.send(lower_source(code, n, g))


def nursery(fds: Sequence[int], n: int, g: int, x64: bool) -> None:
    """The workers' parent: a fresh interpreter that has imported this
    module (jax and the lowering with it: 2 s, paid ONCE a pool) and holds
    no thread, no backend and no array, so it may fork. One worker a
    descriptor; then it reaps them and ends with the last."""
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

    def lower(self, tasks: Sequence[tuple]) -> Tuple[List[Lowered], int, int]:
        """One task a free worker until none is left: the results in the
        tasks' order, how many of them a worker lowered and how many
        workers lowered one. While workers are still starting, a task no
        ready worker is free for is lowered HERE instead of waited with
        (a process's first generation is lowered as before the pool, and
        costs what it cost)."""
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
                conn.send(task)
            if todo and self.starting:
                i, (code, n, g, _) = todo.popleft()
                out[i] = lower_source(code, n, g)
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


def lower_all(codes: Sequence[str], n: int,
              g: int) -> Tuple[List[Lowered], Dict[str, int]]:
    """`lower_source` of every source, in order, and the stage's counters:
    ``pooled`` (sources a worker lowered) and ``workers`` (that lowered at
    least one)."""
    if _size(len(codes)) >= 2:
        x64 = bool(jax.config.jax_enable_x64)
        with _lock:
            pool = _started(len(codes), n, g)
            try:
                if pool is not None:
                    out, pooled, used = pool.lower(
                        [(c, n, g, x64) for c in codes])
                    return out, {"pooled": pooled, "workers": used}
            except Exception as e:  # noqa: BLE001 — never the generation's
                _drop(e)
    return [lower_source(c, n, g) for c in codes], NOT_POOLED


@atexit.register
def _shutdown() -> None:
    """Interpreter exit: kill, do not wait (a parent that dies without
    running this closes its sockets all the same, which ends the
    workers)."""
    if _pool is not None:
        _pool.kill()
