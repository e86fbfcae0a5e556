"""GPU sub-allocation kernels: pick WHICH GPUs on the chosen node.

Vectorized re-design of the reference's list-sort allocators
(reference: simulator/main.py:150-199). Returns a boolean selection mask
over the node's GPU slots instead of index lists.
"""
from __future__ import annotations

import jax.numpy as jnp

# plain int, NOT jnp.int32(...): a module-level jnp scalar would initialize
# a backend at import time (this module is imported before callers get a
# chance to pin jax_platforms — e.g. __graft_entry__.dryrun_multichip)
_BIG = 2**30


def best_fit_gpus(milli_left, gpu_mask, gpu_milli_req, num_gpu):
    """Best-fit: the ``num_gpu`` eligible GPUs with the LEAST free milli,
    ties by ascending slot index (reference main.py:150-177 -- Python's
    stable sort on (milli_left,) preserves index order).

    Args are one node's row: milli_left i32[G], gpu_mask bool[G], scalars.
    Returns (select bool[G], ok bool). ``ok`` is False when fewer than
    ``num_gpu`` eligible GPUs exist (the reference raises ValueError there,
    main.py:164-165). For num_gpu == 0: empty selection, ok=True.
    """
    g = milli_left.shape[0]
    eligible = gpu_mask & (milli_left >= gpu_milli_req)
    # lexicographic (milli_left, index) key per GPU slot; ineligible last.
    # Eligible keys are unique, so a slot's place in the sorted order is
    # the number of keys below its own: G comparisons over the G static
    # slots, no sort and no scatter along the 8-wide GPU axis (which would
    # pin that axis to the chip's 128 lanes under a population vmap;
    # PERF.md section 6, PR 41). Ineligible slots tie at _BIG and are
    # masked out of the selection whatever they count.
    keys = [jnp.where(eligible[i], milli_left[i] * g + i, _BIG)
            for i in range(g)]
    rank = [sum((keys[j] < keys[i]).astype(jnp.int32)
                for j in range(g) if j != i) for i in range(g)]
    select = eligible & (jnp.stack(rank) < num_gpu)
    ok = sum(eligible[i].astype(jnp.int32) for i in range(g)) >= num_gpu
    return select, ok


def first_fit_gpus(milli_left, gpu_mask, gpu_milli_req, num_gpu):
    """First-fit: the first ``num_gpu`` eligible GPUs in slot order
    (reference main.py:179-199, shipped as dead code -- kept for parity)."""
    eligible = gpu_mask & (milli_left >= gpu_milli_req)
    rank = jnp.cumsum(eligible.astype(jnp.int32)) - 1
    select = eligible & (rank < num_gpu)
    ok = jnp.sum(eligible.astype(jnp.int32)) >= num_gpu
    return select, ok
