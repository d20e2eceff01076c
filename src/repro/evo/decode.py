"""`vmap`-able caps_hms-compatible decode: genes → objective vectors.

The host decode (:func:`repro.core.caps_hms.decode_via_heuristic`) is a
sequential modulo-scheduling search; it cannot be vmapped.  This module
implements the *list-scheduling relaxation* the device loop evaluates
instead, over the same segment-packed task tables the PR 4 batched
simulator uses (:func:`repro.sim.vectorized.lower_structure`):

1. **binding scan** — Algorithm 2's greedy channel→memory derivation,
   replayed exactly (sorted channel order, PROD→TILE-PROD→GLOBAL /
   CONS→TILE-CONS→GLOBAL fallback chains, running capacity accounting) as
   a ``lax.scan`` over channels with the *declared* γ (the host's
   enlarge-and-rebind fixpoint is the relaxed part);
2. **ASAP pass** — one dependency-driven pass over actors in topological
   (= arbitration) order gives uncontended task start/finish times, from
   which the capacity enlargement γ̂ of Algorithms 3/4 is estimated with
   the same lifetime formula ``δ + ⌊(F − s_w)/P⌋ + 1``;
3. **period** — the resource lower bound P_lb = max_r Σ τ (Algorithm 4
   line 3, where the host's gallop search *starts*; equal to the exact
   period whenever the schedule is contention-free), or — when the
   problem's objective list asks for ``sim_period`` — the measured
   steady-state period of the phenotype's self-timed execution, obtained
   by lowering genes → (durations, routes, γ̂) *on device* and running the
   shared :func:`repro.sim.vectorized.build_simulate_one` body inside the
   same jit: decode→simulate→rank with no host round-trip.

One :class:`DecodeTables` is built per ξ pattern (the MRB substitution
changes the graph, so tables cannot be shared across patterns — the
explorer buckets the population and LRU-caches tables per pattern) and
everything derived from genes is pure jnp, so ``jax.vmap`` turns the
single-genotype decode into a population decode.

All of this is a *relaxation*: no modulo-window conflict resolution, no
enlarge-rebind fixpoint, single-shot simulation horizon.  The explorer's
relaxed path is therefore gated by a relative-hypervolume tolerance
against the host front, never by bit equality (see DESIGN.md §12).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from ..core.binding import CHANNEL_DECISIONS
from ..core.schedule import Schedule, TaskTimes

__all__ = ["DecodeTables", "RELAXED_OBJECTIVES", "make_relaxed_eval"]

# Objectives the relaxed device decode can produce, and how (see module
# docstring).  Anything else (a user-registered objective) needs the host
# engine — the explorer falls back to the exact path.
RELAXED_OBJECTIVES = ("period", "memory", "core_cost", "comm_volume", "sim_period")

_BIG = np.int64(1) << 40  # sentinel beyond any schedule time


class DecodeTables:
    """Host-precomputed lookup tables for one (ξ pattern, space) pair.

    Everything gene-independent is baked here as numpy arrays; the device
    decode only gathers.  Axis conventions match the batched simulator:
    actors in arbitration order (descending topological priority — also a
    valid ASAP order, since zero-delay edges always point down the
    priority), channels sorted, cores / memories / interconnects sorted.
    """

    def __init__(self, space, xi_bits: Tuple[int, ...], *, pipelined: bool = True):
        from ..core.dse import transformed_graph
        from ..core.schedule import comm_times  # noqa: F401  (doc anchor)
        from ..sim.model import lower_phenotype
        from ..sim.vectorized import lower_structure

        arch = space.arch
        gt = transformed_graph(space, tuple(xi_bits), pipelined)
        self.xi_bits = tuple(xi_bits)
        self.gt = gt

        cores = sorted(arch.cores)
        mems = sorted(arch.memories)
        p_idx = {p: i for i, p in enumerate(cores)}
        q_idx = {q: i for i, q in enumerate(mems)}
        P, Q = len(cores), len(mems)

        # A representative schedule (first allowed core, GLOBAL placement)
        # only to *lower the structure*: the static tables depend on the
        # graph alone, never on this binding.
        beta_a = {a: space.allowed[a][0] for a in gt.actors}
        rep = Schedule(
            period=1,
            times=TaskTimes(),
            actor_binding=beta_a,
            channel_binding={c: arch.global_memory for c in gt.channels},
            capacities={c: gt.channels[c].capacity for c in gt.channels},
        )
        prog = lower_phenotype(gt, arch, rep)
        self.static, _ = lower_structure(prog)
        actors = prog.actors            # arbitration (= topological) order
        channels = prog.channels        # sorted
        ics = sorted(arch.interconnects)
        A, C, H = len(actors), len(channels), len(ics)
        self.A, self.C, self.P, self.Q, self.H = A, C, P, Q, H

        # ---- gene plumbing -------------------------------------------
        # Gene segment lengths follow the *original* space (MRB
        # substitution changes channels, never actors or gene layout).
        self.n_xi_genes = len(space.mcast)
        self.n_cd_genes = len(space.channels)
        self.n_ba_genes = len(space.actors)
        # β_A genes follow space.actors (sorted over the *original* graph;
        # MRB substitution never adds or removes actors).
        gene_pos = {a: i for i, a in enumerate(space.actors)}
        self.ba_gene_of = np.array([gene_pos[a] for a in actors], np.int32)
        jmax = max(len(space.allowed[a]) for a in actors)
        self.allowed_core = np.zeros((A, jmax), np.int32)
        self.n_allowed = np.zeros(A, np.int32)
        for ai, a in enumerate(actors):
            opts = space.allowed[a]
            self.n_allowed[ai] = len(opts)
            for j in range(jmax):
                self.allowed_core[ai, j] = p_idx[opts[j % len(opts)]]
        # C_d genes follow space.channels; an MRB channel inherits its
        # first member's decision (evaluate_genotype's name parsing).
        cpos = {c: i for i, c in enumerate(space.channels)}
        self.cd_gene_of = np.zeros(C, np.int32)
        for ci, c in enumerate(channels):
            if c in cpos:
                self.cd_gene_of[ci] = cpos[c]
            else:
                inner = c[len("mrb{"):-1].split(",")
                self.cd_gene_of[ci] = cpos[inner[0]]

        # ---- architecture tables -------------------------------------
        self.exec_time = np.zeros((A, P), np.int32)
        for ai, a in enumerate(actors):
            for p in cores:
                t = gt.actors[a].exec_times.get(arch.cores[p].ctype)
                self.exec_time[ai, p_idx[p]] = 0 if t is None else t
        self.core_cost = np.array(
            [arch.core_cost(arch.cores[p].ctype) for p in cores], np.float64
        )
        self.mem_cap = np.array(
            [arch.memories[q].capacity for q in mems], np.int64
        )
        # Decision → memory, given the decision's relevant core.
        self.mem_sel = np.zeros((len(CHANNEL_DECISIONS), P), np.int32)
        for di, d in enumerate(CHANNEL_DECISIONS):
            for p in cores:
                if d in ("PROD", "CONS"):
                    q = arch.core_local_memory(p)
                elif d in ("TILE-PROD", "TILE-CONS"):
                    q = arch.tile_local_memory(arch.cores[p].tile)
                else:
                    q = arch.global_memory
                self.mem_sel[di, p_idx[p]] = q_idx[q]
        # τ(φ(c), p, q) per channel (Eq. 11) and route occupancy / hops.
        self.tau = np.zeros((C, P, Q), np.int32)
        self.route_occ = np.zeros((P, Q, max(H, 1)), np.int8)
        h_idx = {h: i for i, h in enumerate(ics)}
        for p in cores:
            for q in mems:
                for h in arch.route_interconnects(p, q):
                    self.route_occ[p_idx[p], q_idx[q], h_idx[h]] = 1
        self.hops = self.route_occ.sum(-1).astype(np.int32)
        for ci, c in enumerate(channels):
            phi = gt.channels[c].token_bytes
            for p in cores:
                for q in mems:
                    self.tau[ci, p_idx[p], q_idx[q]] = arch.comm_time(phi, p, q)

        # ---- channel tables ------------------------------------------
        a_idx = {a: i for i, a in enumerate(actors)}
        self.phi = np.array([gt.channels[c].token_bytes for c in channels], np.int64)
        self.gamma0 = np.array([gt.channels[c].capacity for c in channels], np.int64)
        self.delta = np.array([gt.channels[c].delay for c in channels], np.int64)
        self.prod_a = np.array([a_idx[gt.producer[c]] for c in channels], np.int32)
        self.cons0_a = np.array(
            [a_idx[gt.consumers[c][0]] for c in channels], np.int32
        )
        self.prod_rate = np.array(
            [gt.prod_rate[(gt.producer[c], c)] for c in channels], np.int64
        )
        R = self.static["R"]
        self.reader_a = np.zeros((C, R), np.int32)
        self.read_rate = np.zeros((C, R), np.int64)
        for ci, c in enumerate(channels):
            for ri, r in enumerate(prog.readers[c]):
                self.reader_a[ci, ri] = a_idx[r]
                self.read_rate[ci, ri] = gt.cons_rate[(c, r)]
        # Zero-delay input gate: which channels an actor's window waits on
        # within one iteration (initial tokens break the dependency).
        inmask = self.static["inmask"]          # (A, C, R) bool
        self.in0mask = inmask.any(-1) & (self.delta[None, :] == 0)
        self.outmask = self.static["outmask"]   # (A, C) bool


# ==========================================================================
def make_relaxed_eval(
    tables: DecodeTables,
    objectives: Sequence[str],
    *,
    sim_iters: int = 32,
    mrb_ports: Optional[int] = None,
):
    """Build the fused per-ξ-pattern evaluation: ``genes (N, G) → F (N, k)``.

    Pure JAX, jitted by the caller (the explorer wraps it together with
    ranking + variation into the generation step).  Its device ops fall in
    two named scopes: ``decode`` (genes → objectives, the simulator's
    operands) and ``simulate`` (the fused simulator and its period).  Requires
    ``jax.enable_x64(True)`` at trace time — capacity arithmetic is
    int64 and objective vectors float64.
    """
    unsupported = [o for o in objectives if o not in RELAXED_OBJECTIVES]
    if unsupported:
        raise ValueError(
            f"relaxed device decode cannot produce objectives {unsupported}; "
            f"supported: {RELAXED_OBJECTIVES}"
        )
    import jax
    import jax.numpy as jnp
    from jax import lax

    t = tables
    st = t.static
    A, C, H, Tmax = t.A, t.C, max(t.H, 1), st["Tmax"]
    want_sim = "sim_period" in objectives
    ts_tab = jnp.asarray(st["ts_tab"])          # (A, Tmax, 2+C+R)
    n_tasks = jnp.asarray(st["n_tasks"])        # (A,)
    chan_oh = ts_tab[:, :, 2 : 2 + C]           # (A, Tmax, C)
    is_rd = ts_tab[:, :, 0] > 0
    is_wr = ts_tab[:, :, 1] > 0
    has_chan = is_rd | is_wr
    valid = jnp.arange(Tmax)[None, :] < n_tasks[:, None]
    cidx = jnp.argmax(chan_oh, axis=-1)         # (A, Tmax)
    slot_ch = (chan_oh > 0) & valid[:, :, None]  # (A, Tmax, C)

    allowed = jnp.asarray(t.allowed_core)
    n_allowed = jnp.asarray(t.n_allowed)
    ba_gene_of = jnp.asarray(t.ba_gene_of)
    cd_gene_of = jnp.asarray(t.cd_gene_of)
    exec_time = jnp.asarray(t.exec_time)
    tau = jnp.asarray(t.tau)
    route_occ = jnp.asarray(t.route_occ, jnp.int64)
    hops = jnp.asarray(t.hops, jnp.int64)
    mem_sel = jnp.asarray(t.mem_sel)
    mem_cap = jnp.asarray(t.mem_cap)
    kcost = jnp.asarray(t.core_cost)
    phi = jnp.asarray(t.phi)
    gamma0 = jnp.asarray(t.gamma0)
    delta = jnp.asarray(t.delta)
    prod_a = jnp.asarray(t.prod_a)
    cons0_a = jnp.asarray(t.cons0_a)
    prod_rate = jnp.asarray(t.prod_rate)
    reader_a = jnp.asarray(t.reader_a)
    read_rate = jnp.asarray(t.read_rate)
    reader_mask = jnp.asarray(st["reader_mask"])
    in0mask = jnp.asarray(t.in0mask)
    outmask = jnp.asarray(t.outmask)
    n_xi, n_cd, n_ba = t.n_xi_genes, t.n_cd_genes, t.n_ba_genes
    big = jnp.int64(_BIG)

    if want_sim:
        from ..sim.vectorized import build_simulate_one

        simulate_one, sim_tables = build_simulate_one(st, mrb_ports, sim_iters)

    def eval_one(genes):
        with jax.named_scope("decode"):
            # ---- gene decode -------------------------------------------------
            # Layout [xi | cd | ba]: slices are static (closure constants).
            cd_genes = lax.dynamic_slice_in_dim(genes, n_xi, n_cd)
            ba_genes = lax.dynamic_slice_in_dim(genes, n_xi + n_cd, n_ba)
            j = jnp.remainder(ba_genes[ba_gene_of], n_allowed)
            core = allowed[jnp.arange(A), j]                     # (A,) core idx
            d = cd_genes[cd_gene_of]                             # (C,) decision
            p_rel = jnp.where(d < 2, core[prod_a], core[cons0_a])

            # ---- Algorithm 2: greedy binding with fallback chains ------------
            need = gamma0 * phi
            first_q = mem_sel[d, p_rel]
            # PROD→TILE-PROD and CONS→TILE-CONS; TILE-* and GLOBAL fall back to
            # global directly.
            second_q = jnp.where(
                (d == 0) | (d == 2), mem_sel[jnp.clip(d + 1, 0, 4), p_rel],
                mem_sel[4, p_rel],
            )
            third_q = mem_sel[4, p_rel]

            def bind_step(usage, ins):
                nd, q1, q2, q3 = ins
                ok1 = usage[q1] + nd <= mem_cap[q1]
                ok2 = usage[q2] + nd <= mem_cap[q2]
                q = jnp.where(ok1, q1, jnp.where(ok2, q2, q3))
                return usage.at[q].add(nd), q

            usage0 = jnp.zeros((mem_cap.shape[0],), jnp.int64)
            _, q_of = lax.scan(bind_step, usage0, (need, first_q, second_q, third_q))

            # ---- per-slot durations (Eq. 11 / τ(a, ϑ)) -----------------------
            q_slot = q_of[cidx]                                  # (A, Tmax)
            dur_comm = tau[cidx, core[:, None], q_slot]
            e_a = exec_time[jnp.arange(A), core]
            dur = jnp.where(
                has_chan & valid,
                dur_comm,
                jnp.where(valid & ~has_chan, e_a[:, None], 0),
            ).astype(jnp.int64)

            # ---- ASAP pass (uncontended list schedule) -----------------------
            def asap(k, carry):
                wfin, rfin, wstart = carry
                ws = jnp.max(jnp.where(in0mask[k], wfin, 0))
                ends = ws + jnp.cumsum(dur[k])
                starts = ends - dur[k]
                sc = slot_ch[k]                                  # (Tmax, C)
                r_t = jnp.where(is_rd[k, :, None] & sc, ends[:, None], -big).max(0)
                w_s = jnp.where(is_wr[k, :, None] & sc, starts[:, None], -big).max(0)
                w_f = jnp.where(is_wr[k, :, None] & sc, ends[:, None], -big).max(0)
                rfin = jnp.maximum(rfin, r_t)
                wstart = jnp.where(outmask[k], w_s, wstart)
                wfin = jnp.where(outmask[k], w_f, wfin)
                return wfin, rfin, wstart

            init = (
                jnp.zeros((C,), jnp.int64),
                jnp.full((C,), -big),
                jnp.full((C,), -big),
            )
            _, rfin, wstart = lax.fori_loop(0, A, asap, init)

            # ---- resource loads → period lower bound (Alg. 4, line 3) --------
            window = dur.sum(1)
            core_load = jnp.zeros((t.P,), jnp.int64).at[core].add(window)
            occ = route_occ[core[:, None], q_slot]               # (A, Tmax, H)
            # A masked product + sum, not a contraction: the TPU compiler has no
            # 64-bit integer dot, and this stays exact in integers.
            link_load = jnp.sum(
                (dur * (has_chan & valid))[:, :, None] * occ, axis=(0, 1)
            )
            p_lb = jnp.maximum(
                jnp.int64(1), jnp.maximum(core_load.max(), link_load.max())
            )

            # ---- capacity enlargement estimate (Algorithms 3/4) --------------
            seen = (rfin > -big) & (wstart > -big)
            gamma_hat = jnp.where(
                seen,
                jnp.maximum(gamma0, delta + (rfin - wstart) // p_lb + 1),
                gamma0,
            )
            gamma_hat = jnp.maximum(gamma_hat, 1)

            # ---- objectives --------------------------------------------------
            vals: Dict[str, jnp.ndarray] = {}
            vals["period"] = p_lb.astype(jnp.float64)
            vals["memory"] = (gamma_hat * phi).sum().astype(jnp.float64)
            used = jnp.zeros((t.P,), bool).at[core].set(True)
            vals["core_cost"] = (used * kcost).sum()
            wr_vol = prod_rate * phi * hops[core[prod_a], q_of]
            rd_vol = (
                read_rate
                * phi[:, None]
                * hops[core[reader_a], q_of[:, None]]
                * reader_mask
            ).sum(-1)
            vals["comm_volume"] = (wr_vol + rd_vol).sum().astype(jnp.float64)

        if want_sim:
            with jax.named_scope("simulate"):
                # The shared simulator body keeps int32 state even under the
                # surrounding x64 scope (its integer reductions pin their
                # dtype); only the period math below re-enters float64/int64.
                tb = jnp.concatenate(
                    [
                        dur[:, :, None],
                        occ * (has_chan & valid)[:, :, None],
                    ],
                    axis=-1,
                ).astype(jnp.int32)
                # Compact per-element core remap (an element binds ≤ A cores).
                eq = core[:, None] == core[None, :]
                first = jnp.argmax(eq, axis=1)
                is_first = first == jnp.arange(A)
                compact = jnp.cumsum(is_first) - 1
                core_oh = jax.nn.one_hot(compact[first], A, dtype=bool)
                fire, dead, _ = simulate_one(
                    sim_tables, tb, core_oh, gamma_hat.astype(jnp.int32),
                    jnp.int32(sim_iters),
                )
                vals["sim_period"] = _device_period(jnp, fire, dead, sim_iters)

        with jax.named_scope("decode"):
            return jnp.stack([vals[o] for o in objectives])

    return jax.vmap(eval_one)


def _device_period(jnp, fire, dead, K: int):
    """Device port of :func:`repro.sim.model.measure_period` (+ fallback):
    smallest multiplicity R ≤ 16 whose last 3 R-strided intervals are one
    constant D, per actor, after a quarter-length drain guard; the period
    is the worst actor's D/R, the host's fallback mean-interval estimate
    when any actor's tail never settled, and ``inf`` on deadlock (or a
    wrapped fire buffer)."""
    ts = fire[:, :K]                                  # (A, K) int32
    bad = dead | jnp.any(ts < 0)
    tsl = ts.astype(jnp.int64)
    guard = max(2, K // 4)
    L = K - guard
    rate = jnp.full((ts.shape[0],), jnp.inf, jnp.float64)
    found = jnp.zeros((ts.shape[0],), bool)
    checks = 3
    for m in range(1, 17):
        if L < m * checks + 1:
            break
        d = tsl[:, L - 1] - tsl[:, L - 1 - m]
        ok = jnp.ones_like(found)
        for j in range(2, checks + 1):
            ok = ok & (tsl[:, L - 1 - (j - 1) * m] - tsl[:, L - 1 - j * m] == d)
        take = ok & ~found
        rate = jnp.where(take, d.astype(jnp.float64) / m, rate)
        found = found | ok
    mid = K // 2
    fb = (tsl[:, K - 1] - tsl[:, mid]).astype(jnp.float64) / max(1, K - 1 - mid)
    period = jnp.where(jnp.all(found), rate.max(), fb.max())
    return jnp.where(bad, jnp.inf, period)
