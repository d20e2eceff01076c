"""Batched k-objective NSGA-II ranking as pure JAX ops.

Three device primitives over an objective matrix ``F`` of shape (N, k)
(all objectives minimized, ``inf`` = infeasible / diverged coordinate):

* :func:`domination_matrix` — pairwise strict Pareto dominance;
* :func:`nondomination_ranks` — iterative front peeling (the fixpoint of
  :func:`repro.core.pareto.fast_nondominated_sort`);
* :func:`crowding` — crowding distance of *all* fronts in one pass: a
  single lexsort per objective groups each front into a contiguous
  segment, segment boundaries get ``inf``, interior points accumulate
  (next − prev) / (max − min) with the same ``inf``-safe rules as the
  host implementation.  The relaxed device-resident loop uses it, under
  ``jax.enable_x64(True)``, with row order as the tie key.

The exact-evaluation path (:func:`parity_rank_crowd`) must reproduce the
host explorer's ``rank_crowd`` bit for bit, and a TPU has no native
float64.  So the device part there is integer only: objectives become
order-preserving int32 keys (dense per-objective ranks, ``inf`` the
largest), whose domination matrix equals the floats' exactly.  The host
replays the front *sequence* from that matrix (same S-lists, same
counters as :func:`fast_nondominated_sort`) and computes crowding with
the reference's own :func:`repro.core.pareto.crowding_distance`, so every
float is the host's by construction.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

__all__ = [
    "domination_matrix",
    "nondomination_ranks",
    "crowding",
    "truncation_order",
    "host_front_sequence",
    "parity_rank_crowd",
]


def _jnp():
    import jax  # deferred: importing repro.evo must not pay for jax

    return jax, jax.numpy


# ------------------------------------------------------------- device ops
def domination_matrix(F):
    """dom[i, j] ⇔ F[i] strictly Pareto-dominates F[j] (N, N) bool."""
    _, jnp = _jnp()
    F = jnp.asarray(F)
    le = jnp.all(F[:, None, :] <= F[None, :, :], axis=-1)
    lt = jnp.any(F[:, None, :] < F[None, :, :], axis=-1)
    return le & lt


def nondomination_ranks(F):
    """Front index per row (0 = nondominated), int32 (N,).

    Iterative peeling: front r = rows not dominated by any still-unranked
    row — exactly the fixpoint :func:`fast_nondominated_sort` computes with
    its decrement counters, so ``ranks[i] == front_index_of(i)`` always.
    """
    jax, jnp = _jnp()
    F = jnp.asarray(F)
    n = F.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    dom = domination_matrix(F)

    def cond(state):
        rank, r = state
        return jnp.any(rank < 0) & (r < n)

    def body(state):
        rank, r = state
        remaining = rank < 0
        cnt = jnp.sum(dom & remaining[:, None] & remaining[None, :], axis=0)
        front = remaining & (cnt == 0)
        return jnp.where(front, r, rank), r + 1

    rank, _ = jax.lax.while_loop(
        cond, body, (jnp.full((n,), -1, jnp.int32), jnp.int32(0))
    )
    return rank


def crowding(F, ranks):
    """Crowding distance per row, all fronts at once, float64 (N,).

    Equal values inside a front are ordered by row.  The rules are the
    host :func:`repro.core.pareto.crowding_distance`'s: per objective,
    front boundaries are *set* to ``inf`` (overwriting any accumulation),
    zero-span objectives contribute nothing, infinite spans contribute
    ``inf`` exactly when one neighbour is infinite and the other finite,
    and finite spans accumulate (next − prev) / span in objective order.
    """
    jax, jnp = _jnp()
    lax = jax.lax
    F = jnp.asarray(F, jnp.float64)
    n, m = F.shape
    if n == 0:
        return jnp.zeros((0,), jnp.float64)
    ranks = jnp.asarray(ranks, jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)
    idx = jnp.arange(n)
    inf = jnp.float64(jnp.inf)
    d = jnp.zeros((n,), jnp.float64)
    for k in range(m):
        v = F[:, k]
        # Fronts become contiguous segments, each sorted by value with the
        # host's stable tie order.
        order = jnp.lexsort((pos, v, ranks))
        vs = v[order]
        seg = ranks[order]
        is_first = jnp.concatenate([jnp.array([True]), seg[1:] != seg[:-1]])
        is_last = jnp.concatenate([seg[1:] != seg[:-1], jnp.array([True])])
        start = lax.cummax(jnp.where(is_first, idx, -1), axis=0)
        end = jnp.flip(lax.cummin(jnp.flip(jnp.where(is_last, idx, n)), axis=0))
        lo, hi = vs[start], vs[end]
        span = hi - lo
        nxt = vs[jnp.minimum(idx + 1, n - 1)]
        prv = vs[jnp.maximum(idx - 1, 0)]
        gap = nxt - prv
        interior = (~is_first) & (~is_last)
        contrib = jnp.where(
            jnp.isinf(span), jnp.where(jnp.isinf(gap), inf, 0.0), gap / span
        )
        contrib = jnp.where(interior & (hi != lo), contrib, 0.0)
        boundary = is_first | is_last
        # Scatter back to row order: boundaries overwrite (host `d[i]=inf`),
        # interiors accumulate — one add per objective, objectives in order.
        add = jnp.zeros((n,), jnp.float64).at[order].set(contrib)
        bnd = jnp.zeros((n,), bool).at[order].set(boundary)
        d = jnp.where(bnd, inf, d + add)
    return d


def truncation_order(ranks, crowd):
    """Stable elitist order: by (rank, −crowding), ties by row index —
    the device form of ``sorted(range(n), key=(rank, -crowd))``."""
    _, jnp = _jnp()
    n = ranks.shape[0]
    return jnp.lexsort(
        (jnp.arange(n), -jnp.asarray(crowd), jnp.asarray(ranks))
    )


# ------------------------------------------------- host-parity front order
def host_front_sequence(dom: np.ndarray) -> List[List[int]]:
    """Replay :func:`fast_nondominated_sort`'s exact front *sequence* from
    a precomputed domination matrix.  The host's within-front order is an
    artifact of its S-list traversal (ascending ``j`` per dominator, front
    members in discovery order); crowding tie-breaks depend on it, so the
    parity path reconstructs it instead of guessing."""
    n = dom.shape[0]
    S = [list(np.nonzero(dom[i])[0]) for i in range(n)]
    counts = dom.sum(axis=0).astype(int)
    fronts: List[List[int]] = [[i for i in range(n) if counts[i] == 0]]
    k = 0
    while fronts[k]:
        nxt: List[int] = []
        for i in fronts[k]:
            for j in S[i]:
                counts[j] -= 1
                if counts[j] == 0:
                    nxt.append(int(j))
        k += 1
        fronts.append(nxt)
    return [f for f in fronts if f]


def _order_keys(F: np.ndarray) -> np.ndarray:
    """Dense per-objective ranks of ``F`` as int32: ``F[i,k] < F[j,k]`` ⇔
    ``keys[i,k] < keys[j,k]`` (ties and ``inf`` included), so dominance
    over the keys is dominance over the floats."""
    return np.stack(
        [np.unique(F[:, k], return_inverse=True)[1] for k in range(F.shape[1])],
        axis=1,
    ).astype(np.int32)


_DOMINATION_JIT = None


def _ranked_domination(keys):
    """:func:`domination_matrix` as the device part ``rank`` of a step."""
    jax, _ = _jnp()
    with jax.named_scope("rank"):
        return domination_matrix(keys)


def parity_rank_crowd(
    objs: Sequence[Sequence[float]],
) -> Tuple[Dict[int, int], Dict[int, float]]:
    """Drop-in replacement for the host explorer's ``rank_crowd``:
    domination on device over int32 order keys, front sequence and
    crowding on the host — the same ``(rank, crowd)`` dicts bit-for-bit."""
    global _DOMINATION_JIT
    from ..core.pareto import crowding_distance

    n = len(objs)
    if n == 0:
        return {}, {}
    if _DOMINATION_JIT is None:
        import jax

        from ..devices import ensure_compile_cache

        ensure_compile_cache()
        _DOMINATION_JIT = jax.jit(_ranked_domination)
    dom = np.asarray(_DOMINATION_JIT(_order_keys(np.asarray(objs, np.float64))))
    rank: Dict[int, int] = {}
    crowd: Dict[int, float] = {}
    for fi, front in enumerate(host_front_sequence(dom)):
        d = crowding_distance(objs, front)
        for i in front:
            rank[i] = fi
            crowd[i] = d[i]
    return rank, crowd
