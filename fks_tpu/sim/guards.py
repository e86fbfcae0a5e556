"""Numerics watchdog guards: mask-and-flag NaN/Inf/range detection.

The watchdog's device half. ``jax.experimental.checkify`` lifts errors out
of jitted code but composes poorly with the repo's loop shapes
(``vmap``-of-``while_loop`` bodies under ``shard_map`` — checkify
functionalization inserts per-lane error state the manual-axes audit
rejects), so guards are plain elementwise masks instead: ``isfinite`` +
``where`` survive ``vmap``/``shard_map`` trivially because they ARE the
ops the engines are built from. Violations accumulate as a sticky int32
bitmask in the engine carry (``SimState.numeric_flags`` /
``FlatState.numeric_flags``) and surface in ``SimResult.numeric_flags``;
per-lane under ``vmap`` because the flags live in the per-lane state
pytree, so one lane's NaN never poisons a sibling lane.

All guards are gated on the Python-static ``SimConfig.watchdog`` flag: the
branch resolves at trace time, so the disabled path contributes zero ops
to the compiled program and is bit-identical to a build without guards.
When a guard fires, the offending scores are masked to 0 ("refuse", the
engines' no-placement sentinel) — identity for finite inputs, so an
enabled watchdog is also bit-identical whenever no violation fires.

The host half lives above: event emission and the divergence audit in
``fks_tpu.obs.watchdog``, the parity sentinel in ``fks_tpu.funsearch.parity``.
"""
from __future__ import annotations

from typing import Any, List

import jax.numpy as jnp
import numpy as np

#: sticky violation bits carried in ``numeric_flags``
FLAG_NAN = 1    # a policy score or the fitness was NaN
FLAG_INF = 2    # ... was +/-Inf
FLAG_RANGE = 4  # the final fitness left [0, 1]

FLAG_NAMES = ((FLAG_NAN, "nan"), (FLAG_INF, "inf"), (FLAG_RANGE, "range"))


def describe_flags(mask: int) -> List[str]:
    """Human-readable names for a violation bitmask (host-side)."""
    return [name for bit, name in FLAG_NAMES if int(mask) & bit]


def combined_flags(numeric_flags: Any) -> int:
    """OR-reduce a result's flag mask — a scalar, a per-lane array, or a
    nested batch — to one Python int (host-side)."""
    arr = np.asarray(numeric_flags)
    if arr.size == 0:
        return 0
    return int(np.bitwise_or.reduce(arr.reshape(-1).astype(np.int64)))


def score_flags(raw_scores, gate):
    """i32 violation bitmask for one policy invocation's node scores.

    ``gate`` is the step's "this score is consumed" predicate (the engines'
    ``create``): scores computed but discarded on deletion events must not
    flag. Integer score dtypes cannot hold NaN/Inf, so the check is a
    trace-time no-op there (returns a constant 0).
    """
    scores = jnp.asarray(raw_scores)
    if not jnp.issubdtype(scores.dtype, jnp.floating):
        return jnp.int32(0)
    flags = (jnp.any(jnp.isnan(scores)).astype(jnp.int32) * FLAG_NAN
             + jnp.any(jnp.isinf(scores)).astype(jnp.int32) * FLAG_INF)
    return jnp.where(gate, flags, 0).astype(jnp.int32)


def sanitize_scores(raw_scores):
    """Mask non-finite policy scores to 0 — the engines' "refuse placement"
    sentinel, so a NaN lane degrades to an unplaced pod instead of feeding
    an implementation-defined argmax. Identity for finite inputs (and for
    integer dtypes, statically)."""
    scores = jnp.asarray(raw_scores)
    if not jnp.issubdtype(scores.dtype, jnp.floating):
        return raw_scores
    return jnp.where(jnp.isfinite(scores), scores, jnp.zeros_like(scores))


def guard_scores(raw_scores, gate, numeric_flags, *, enabled: bool):
    """The engines' per-invocation watchdog step in one call: fold this
    invocation's violation bits into the sticky carry mask, then sanitize.
    Returns ``(scores, numeric_flags)`` — unchanged when ``enabled`` is
    False (Python-static, zero ops on the disabled path). Shared by the
    exact and flat engines so the guard semantics cannot drift; the score
    vector's length is irrelevant (flags are per-EVENT, any NaN anywhere
    in the scored view flags it), so the same call guards the dense [N]
    sweep and the prefiltered [k] candidate view — no index translation
    through the top-k gather is needed or wanted."""
    if not enabled:
        return raw_scores, numeric_flags
    return (sanitize_scores(raw_scores),
            numeric_flags | score_flags(raw_scores, gate))


def fitness_flags(score):
    """i32 violation bitmask for a final fitness scalar: NaN, Inf, or
    outside the paper's [0, 1] fitness range."""
    score = jnp.asarray(score)
    nan = jnp.isnan(score)
    inf = jnp.isinf(score)
    rng = ~nan & ~inf & ((score < 0) | (score > 1))
    return (nan.astype(jnp.int32) * FLAG_NAN
            + inf.astype(jnp.int32) * FLAG_INF
            + rng.astype(jnp.int32) * FLAG_RANGE)
