"""JAX-vectorized self-timed simulator: fused actor-parallel rounds.

Executes the same dynamical system as :mod:`repro.sim.events` (the
normative spec lives in :mod:`repro.sim.model`) on dense ``jnp`` state
arrays.  The hot path is throughput-shaped (ISSUE 4 rebuilt it):

* the whole simulation is ONE flattened ``lax.while_loop`` — each
  iteration is one synchronous phased round of the model discipline, and
  when the instant is quiescent the same iteration advances time to the
  next task completion (no nested fixpoint/step loop towers, which
  serialize badly under ``vmap``);
* a round is *data-parallel over the actors*: every actor's current task
  is selected from segment-packed dense task planes (per-actor task rows
  padded to ``Tmax``, each task's fields packed into a few int32 words:
  one graph-derived code, its duration, its channel's γ, its route
  bitmask) by masked reductions over ``Tmax``, unpacked with integer ops;
  interconnect conflicts are bitmask ANDs; completions / enabling /
  priority arbitration / state updates are masked array expressions —
  **no per-actor loop, no ragged gathers, no scatters** anywhere in the
  compiled body;
* the firing-count target ``K`` is a *runtime* operand; the fire buffer
  is sized to the power-of-two bucket of the requested firings and batch
  sizes are bucketed to powers of two, so horizon-doubling reruns and
  sub-batch retries compile at most once per bucket;
* compiled functions are cached per structure in ``_COMPILED`` and on
  disk in the repo's one persistent compile cache
  (:func:`repro.devices.ensure_compile_cache`), and ``REPRO_SIM_FAST_CPU``
  configures XLA:CPU for this dispatch-bound loop shape when the process
  is explicitly on the CPU (see :func:`_wire_fast_cpu`).

The batch must share one (graph, architecture) pair — the task *structure*
(actor order, task kinds, channels, reader slots) is graph-derived and
becomes static arrays baked into the compiled step function; everything
binding-dependent (durations, routes, core indices, capacities) is batched.

Backend equality is an enforced invariant: per-actor firing-time sequences
are bit-identical to the event-driven backend on every phenotype (the
parity suite asserts this), so periods measured by the shared
:func:`~repro.sim.model.measure_period` agree exactly — including the
per-element horizon-doubling policy, which mirrors ``events.simulate``.
The Pallas backend (:mod:`repro.kernels.sim_step`) reuses this module's
single-element round machinery, so all three backends share one semantics.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..devices import ensure_compile_cache
from ..core.architecture import ArchitectureGraph
from ..core.graph import ApplicationGraph
from ..core.schedule import Schedule
from .events import SimResult
from .model import (
    READ,
    WRITE,
    SimConfig,
    SimProgram,
    fallback_period,
    lower_phenotype,
    measure_period,
    predict_horizon,
)

__all__ = [
    "batch_simulate",
    "batch_simulate_periods",
    "INT32_SAFE_HORIZON",
    "BATCH_BACKENDS",
    "trace_count",
]

_I32_INF = np.int32(2**31 - 1)
# Above this predicted event-time horizon int32 state could overflow; the
# wrapper falls back to the event-driven backend (Python ints are exact).
INT32_SAFE_HORIZON = 2**30

BATCH_BACKENDS = ("vectorized", "pallas")

_COMPILED: Dict[Tuple, object] = {}

# Interconnects per int32 route bitmask word (the sign bit stays clear).
_ROUTE_BITS = 31

# Incremented every time a simulator function is (re)traced — the
# retrace-regression test asserts structure-identical batches reuse the
# compiled function instead of tracing again.
_TRACE_COUNT = 0


def trace_count() -> int:
    """How many times a batched simulator has been traced this process."""
    return _TRACE_COUNT


_FAST_CPU_WIRED = False


def _wire_fast_cpu() -> None:
    """Configure XLA:CPU for latency-bound loop dispatch, if possible.

    The compiled simulator is one long sequential ``while`` loop of tiny
    fused kernels; under the default thunk runtime every kernel pays a
    multi-microsecond executor handoff (bounced between cores on
    multi-CPU hosts), which dominates wall time at these sizes.  Two
    measured fixes, both only applicable before the JAX CPU backend
    initializes (so this is best-effort — a no-op when the process
    already used JAX):

    * compile whole programs through the legacy single-function CPU
      runtime (``--xla_cpu_use_thunk_runtime=false``) — the loop becomes
      one LLVM function with no per-kernel dispatch (~2.5x here);
    * initialize the backend under single-CPU affinity so its intra-op
      pool gets one thread and kernels never migrate cores mid-loop
      (~2x); the affinity is restored immediately after init.

    Acts only when the process is explicitly on the CPU
    (``jax_platforms == "cpu"``): with the platform unset, initializing the
    backend here could take an accelerator under a one-CPU affinity mask.
    Disable with ``REPRO_SIM_FAST_CPU=0``.
    """
    global _FAST_CPU_WIRED
    if _FAST_CPU_WIRED:
        return
    _FAST_CPU_WIRED = True
    if os.environ.get("REPRO_SIM_FAST_CPU", "1") in ("0", ""):
        return
    import jax
    from jax._src import xla_bridge

    if jax.config.jax_platforms != "cpu" or xla_bridge.backends_are_initialized():
        return  # not explicitly CPU, or too late to influence flags/pool
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_cpu_use_thunk_runtime" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_cpu_use_thunk_runtime=false"
        ).strip()
    try:
        full = os.sched_getaffinity(0)
    except AttributeError:  # non-Linux: still use the legacy runtime
        jax.devices()
        return
    try:
        os.sched_setaffinity(0, {min(full)})
        jax.devices()  # backend init sizes its thread pool now
    finally:
        os.sched_setaffinity(0, full)


# --------------------------------------------------------------- lowering
def _structure_key(prog: SimProgram, cfg: SimConfig) -> Tuple:
    return (
        tuple(prog.actors),
        tuple(
            (t.kind, t.channel, t.reader_slot)
            for a in prog.actors
            for t in prog.tasks[a]
        ),
        tuple(prog.channels),
        tuple(prog.delay[c] for c in prog.channels),
        tuple(tuple(prog.readers[c]) for c in prog.channels),
        tuple(sorted(prog.arch.cores)),
        tuple(sorted(prog.arch.interconnects)),
        cfg.max_iterations,
        cfg.mrb_ports,
    )


def _pack_task_code(is_read: bool, is_write: bool, chan: int, slot: int, C: int) -> int:
    """One task's graph-derived fields as one int32 of bit fields:
    ``is_read | is_write << 1 | (chan + 1) << 2 | (slot + 1) << (2 + CB)``
    with ``CB = C.bit_length()`` (the ``chan + 1`` field holds 0..C), where
    ``chan`` / ``slot`` are −1 for a task without a channel / reader slot.
    Padding slots hold 0, which unpacks to the all-zero descriptor; shifts
    and masks unpack it, no integer division."""
    return (
        int(is_read) | int(is_write) << 1 | (chan + 1) << 2
        | (slot + 1) << (2 + C.bit_length())
    )


def _unpack_task_code(code, c_iota, s_iota, C: int):
    """Inverse of :func:`_pack_task_code` on a vector of codes, with integer
    ops only (numpy or jnp alike): ``(is_read, is_write, chan one-hot,
    slot one-hot)``, the one-hots compared against ``c_iota = 0..C-1`` and
    ``s_iota = 0..R-1``."""
    cb = C.bit_length()
    chan = ((code >> 2) & ((1 << cb) - 1)) - 1
    slot = (code >> (2 + cb)) - 1
    return (
        (code & 1) > 0,
        (code & 2) > 0,
        chan[:, None] == c_iota[None],
        slot[:, None] == s_iota[None],
    )


def _lower_batch(progs: Sequence[SimProgram]):
    """Static structure arrays (graph-derived, shared) + batched arrays
    (binding-derived, per phenotype), in segment-packed dense layout: every
    per-task table is padded to ``Tmax`` tasks per actor so the step body
    can select the current task with a masked reduction instead of a ragged
    gather."""
    p0 = progs[0]
    actors = p0.actors
    channels = p0.channels
    ics = sorted(p0.arch.interconnects)
    c_idx = {c: i for i, c in enumerate(channels)}
    h_idx = {h: i for i, h in enumerate(ics)}
    A, C, H = len(actors), len(channels), len(ics)
    R = max((len(p0.readers[c]) for c in channels), default=1)
    Tmax = max(len(p0.tasks[a]) for a in actors)

    n_tasks = np.array([len(p0.tasks[a]) for a in actors], np.int32)
    if _pack_task_code(True, True, C - 1, R - 1, C) > np.iinfo(np.int32).max:
        raise ValueError(f"task codes of {C} channels x {R} reader slots overflow int32")
    # Graph-derived per-task fields as one-hot columns [is_read, is_write,
    # chan one-hot (C), reader-slot one-hot (R)] — read once by the device
    # decode's ASAP pass — and the same fields as one int32 code per task,
    # which the simulator's rounds select (see _pack_task_code).
    ts_tab = np.zeros((A, Tmax, 2 + C + R), np.int32)
    task_code = np.zeros((A, Tmax), np.int32)
    for ai, a in enumerate(actors):
        for ti, t in enumerate(p0.tasks[a]):
            ts_tab[ai, ti, 0] = t.kind == READ
            ts_tab[ai, ti, 1] = t.kind == WRITE
            if t.channel is not None:
                ts_tab[ai, ti, 2 + c_idx[t.channel]] = 1
            if t.reader_slot >= 0:
                ts_tab[ai, ti, 2 + C + t.reader_slot] = 1
            task_code[ai, ti] = _pack_task_code(
                t.kind == READ, t.kind == WRITE,
                c_idx[t.channel] if t.channel is not None else -1,
                t.reader_slot, C,
            )

    reader_mask = np.zeros((C, R), bool)
    delay = np.zeros(C, np.int32)
    for c in channels:
        reader_mask[c_idx[c], : len(p0.readers[c])] = True
        delay[c_idx[c]] = p0.delay[c]
    # Start-of-firing gates: which (channel, slot) views actor a reads, and
    # which channels it writes (bounded-buffer enabling rule).
    inmask = np.zeros((A, C, R), bool)
    outmask = np.zeros((A, C), bool)
    for ai, a in enumerate(actors):
        for t in p0.tasks[a]:
            if t.kind == READ:
                inmask[ai, c_idx[t.channel], t.reader_slot] = True
            elif t.kind == WRITE:
                outmask[ai, c_idx[t.channel]] = True

    B = len(progs)
    # Binding-derived per-task fields: [duration, route occupancy (H)] —
    # batched because bindings differ per phenotype (the simulator packs
    # the routes into bitmask words once per call).
    # Cores are remapped per element to a compact 0..A-1 index space (an
    # element binds at most A distinct cores, usually far fewer than the
    # architecture has) so the per-round core-arbitration arrays stay
    # A-wide instead of |cores|-wide.
    tb_tab = np.zeros((B, A, Tmax, 1 + H), np.int32)
    core_oh = np.zeros((B, A, A), bool)
    gamma = np.ones((B, C), np.int32)
    for b, pr in enumerate(progs):
        cmap: Dict[str, int] = {}
        for ai, a in enumerate(actors):
            core = pr.core_of[a]
            ci = cmap.setdefault(core, len(cmap))
            core_oh[b, ai, ci] = True
            for ti, t in enumerate(pr.tasks[a]):
                tb_tab[b, ai, ti, 0] = t.duration
                for h in t.route:
                    tb_tab[b, ai, ti, 1 + h_idx[h]] = 1
        for c in channels:
            gamma[b, c_idx[c]] = pr.capacity[c]

    static = dict(
        A=A, C=C, P=A, H=H, R=R, Tmax=Tmax,
        n_tasks=n_tasks, ts_tab=ts_tab, task_code=task_code,
        reader_mask=reader_mask, delay=delay, inmask=inmask, outmask=outmask,
    )
    batched = dict(tb=tb_tab, core_oh=core_oh, gamma=gamma)
    return static, batched


def lower_structure(prog: SimProgram):
    """Public seam over :func:`_lower_batch` for a single program: returns
    ``(static, batched)`` where ``static`` holds the graph-derived
    segment-packed structure tables (shareable across any binding of the
    same transformed graph) and ``batched`` the program's own
    binding-derived arrays with a leading batch axis of 1.  The
    device-resident evolutionary decode (:mod:`repro.evo.decode`) lowers
    one representative phenotype per ξ pattern this way, then synthesizes
    the batched arrays *on device* from genotype matrices."""
    return _lower_batch([prog])


# --------------------------------------------------------------- simulator
def build_simulate_one(static, ports: Optional[int], k_max: int):
    """Single-phenotype simulator for one structure: a pure JAX function

        ``simulate_one(tables, tb, core_oh, gamma, K) -> (fire, dead, t)``

    with ``K`` (firings per actor) a *runtime* scalar and the fire buffer
    statically ``(A, k_max)``.  Each loop iteration is one synchronous
    phased round of the model discipline, computed *data-parallel over the
    actors*: the current task of every actor is selected from the
    segment-packed dense task planes (one packed int32 code per task for
    the graph-derived fields, plus duration, channel-γ and route-bitmask
    planes packed from the call's operands once per call) with masked
    reductions over ``Tmax``, completions/candidates/arbitration are masked
    array expressions, and there is no per-actor loop, gather or scatter
    anywhere — XLA fuses a round into a few dozen kernels regardless of
    actor count.  Returns ``(simulate_one, tables)`` where ``tables`` is
    the tuple of graph-derived structure arrays ``simulate_one`` expects
    as its first argument — explicit operands (not closure constants) so
    the function body can also serve as a Pallas kernel body.  Shared by
    the ``vmap``-batched lax backend below and the Pallas kernel in
    :mod:`repro.kernels.sim_step` — one implementation, three backends.
    """
    import jax.numpy as jnp
    from jax import lax

    A = static["A"]
    C = static["C"]
    R = static["R"]
    H = static["H"]
    Tmax = static["Tmax"]
    W = max(1, -(-H // _ROUTE_BITS))  # route bitmask words per task
    tables = (
        static["task_code"],        # (A,Tmax) packed graph-derived fields
        static["n_tasks"],          # (A,)
        static["reader_mask"],      # (C,R)
        static["delay"],            # (C,)
        static["inmask"],           # (A,C,R)
        static["outmask"],          # (A,C)
    )
    total_tasks = int(static["n_tasks"].sum())
    NEG, BIG = -1, A

    def simulate_one(tables, tb, core_oh, gamma, K):
        global _TRACE_COUNT
        _TRACE_COUNT += 1
        task_code, n_tasks, reader_mask, delay, inmask, outmask = tables
        aidx = jnp.arange(A, dtype=jnp.int32)
        t_iota = jnp.arange(Tmax, dtype=jnp.int32)
        c_iota = jnp.arange(C, dtype=jnp.int32)
        s_iota = jnp.arange(R, dtype=jnp.int32)
        b_iota = jnp.arange(_ROUTE_BITS, dtype=jnp.int32)
        k_iota = jnp.arange(int(k_max), dtype=jnp.int32)
        # lower_tri[i, j] ⇔ j strictly precedes i in arbitration order
        lower_tri = aidx[:, None] > aidx[None, :]

        def avail_of(omega, rho):
            return jnp.where(
                reader_mask & (rho != NEG),
                ((omega[:, None] - rho - 1) % gamma[:, None]) + 1,
                0,
            )                                                      # (C,R)

        def words_of(mask):
            # (..., H) bool → (..., W) int32: bit b of word w ⇔ element 31·w + b.
            mask = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, W * _ROUTE_BITS - H)])
            return jnp.sum(
                jnp.where(mask.reshape(mask.shape[:-1] + (W, _ROUTE_BITS)), 1 << b_iota, 0),
                axis=-1, dtype=jnp.int32,
            )

        # One (Tmax,A) int32 plane per packed per-task field: graph code,
        # duration, γ of the task's channel, route bitmask words (the
        # interconnects the task occupies).  Built once per call; every
        # round selects all planes with sibling masked reductions over
        # Tmax, a major axis.
        chan_tab = _unpack_task_code(task_code.reshape(A * Tmax), c_iota, s_iota, C)[2]
        gamma_tab = jnp.sum(jnp.where(chan_tab, gamma[None], 0), axis=1, dtype=jnp.int32)
        route_words = words_of(tb[:, :, 1:] > 0)                   # (A,Tmax,W)
        planes = [task_code.T, tb[:, :, 0].T, gamma_tab.reshape(A, Tmax).T] + [
            route_words[:, :, w].T for w in range(W)
        ]

        def descriptor(cur):
            # Current-task descriptor for every actor: a masked reduction
            # over Tmax of each packed plane, unpacked by integer ops (see
            # _unpack_task_code).  cur == n_tasks between windows — the
            # all-zero selection then yields don't-care fields, gated out
            # by in_w everywhere.
            cur_oh = t_iota[None, :] == cur[:, None]               # (A,Tmax)
            sel = [
                jnp.sum(jnp.where(cur_oh.T, p, 0), axis=0, dtype=jnp.int32) for p in planes
            ]                                                      # (A,) each
            d = {}
            d["is_read"], d["is_write"], c_oh, s_oh = _unpack_task_code(
                sel[0], c_iota, s_iota, C
            )                                                      # (A,C), (A,R)
            d["c_oh"] = c_oh
            d["dur_t"] = sel[1]
            d["gamma_c"] = jnp.maximum(sel[2], 1)
            d["route_w"] = jnp.stack(sel[3:])                      # (W,A)
            bits = (d["route_w"].T[:, :, None] >> b_iota) & 1      # (A,W,31)
            d["route_t"] = bits.reshape(A, W * _ROUTE_BITS)[:, :H] > 0  # (A,H)
            d["cs_mask"] = c_oh[:, :, None] & s_oh[:, None, :]     # (A,C,R)
            d["timed"] = d["dur_t"] > 0
            return d

        def read_adv(cs_mask, gamma_c, avail, rho):
            # Each reader's post-read ρ view (−1 when its window empties).
            avail_t = jnp.sum(jnp.where(cs_mask, avail[None], 0), axis=(1, 2), dtype=jnp.int32)
            rho_cs = jnp.sum(jnp.where(cs_mask, rho[None], 0), axis=(1, 2), dtype=jnp.int32)
            return avail_t, jnp.where(
                avail_t == 1, NEG, (rho_cs + 1) % gamma_c
            )

        def apply_reads(cs_mask, who, rho_adv, rho):
            m = who[:, None, None] & cs_mask                       # (A,C,R)
            return jnp.where(
                jnp.any(m, axis=0),
                jnp.sum(jnp.where(m, rho_adv[:, None, None], 0), axis=0, dtype=jnp.int32),
                rho,
            )

        def apply_writes(c_oh, who, omega, rho):
            written = jnp.any(who[:, None] & c_oh, axis=0)         # (C,)
            rho = jnp.where(
                written[:, None] & reader_mask & (rho == NEG),
                omega[:, None],
                rho,
            )
            return jnp.where(written, (omega + 1) % gamma, omega), rho

        def finish_windows(done_now, cur, in_w, iters, owner):
            wdone = done_now & (cur + 1 == n_tasks)
            cur = jnp.where(done_now, cur + 1, cur)
            in_w = in_w & ~wdone
            iters = iters + wdone.astype(jnp.int32)
            released = jnp.any(wdone[:, None] & core_oh, axis=0)
            return cur, in_w, iters, jnp.where(released, NEG, owner)

        def round_fn(state):
            (t, omega, rho, active, owner, ic_busy,
             in_w, running, busy, cur, iters, fire,
             run_read, run_write, run_coh, run_cs, run_gc) = state

            # ---- completion phase: effects of the tasks that were
            # running; their descriptor fields were recorded when they
            # started (run_*), so no task-table selection happens here.
            # Reads apply before writes, each group touching disjoint
            # state.  Only timed tasks ever run, so every due task also
            # releases its channel port.
            due = running & (busy <= t)
            running = running & ~due
            active = active - jnp.sum(
                (due[:, None] & run_coh).astype(jnp.int32), axis=0,
                dtype=jnp.int32,
            )
            _, rho_adv = read_adv(run_cs, run_gc, avail_of(omega, rho), rho)
            rho = apply_reads(run_cs, due & run_read, rho_adv, rho)
            omega, rho = apply_writes(run_coh, due & run_write, omega, rho)
            cur, in_w, iters, owner = finish_windows(due, cur, in_w, iters, owner)

            # ---- start phase: window starts first (rule 1, arbitrated
            # per core), then task-start candidates from the state with
            # the winners' windows open — a firing actor's first task
            # competes in the same round.
            avail = avail_of(omega, rho)
            free = gamma - jnp.max(jnp.where(reader_mask, avail, 0), axis=1)
            owner_of = jnp.sum(jnp.where(core_oh, owner[None], 0), axis=1, dtype=jnp.int32)
            in_bad = jnp.any(inmask & (avail[None] < 1), axis=(1, 2))
            out_bad = jnp.any(outmask & (free[None] < 1), axis=1)
            fire_cand = (
                ~in_w & (iters < K) & (owner_of == NEG) & ~in_bad & ~out_bad
            )
            # Per core the highest-priority window-start candidate wins.
            cand_idx = jnp.where(fire_cand[:, None] & core_oh, aidx[:, None], BIG)
            min_idx = jnp.min(cand_idx, axis=0)                    # (P,)
            fire_win = fire_cand & jnp.any(
                core_oh & (cand_idx == min_idx[None]), axis=1
            )
            claimed = jnp.any(fire_win[:, None] & core_oh, axis=0)
            claim_idx = jnp.sum(
                jnp.where(fire_win[:, None] & core_oh, aidx[:, None], 0),
                axis=0, dtype=jnp.int32,
            )
            owner = jnp.where(claimed, claim_idx, owner)
            in_w = in_w | fire_win
            fire = jnp.where(
                fire_win[:, None] & (k_iota[None] == iters[:, None]), t, fire
            )
            cur = jnp.where(fire_win, 0, cur)

            d = descriptor(cur)
            is_read, is_write = d["is_read"], d["is_write"]
            c_oh, route_t, route_w, timed, dur_t = (
                d["c_oh"], d["route_t"], d["route_w"], d["timed"], d["dur_t"]
            )
            avail_t, rho_adv = read_adv(d["cs_mask"], d["gamma_c"], avail, rho)
            free_c = jnp.sum(jnp.where(c_oh, free[None], 0), axis=1, dtype=jnp.int32)
            cand = (
                (in_w & ~running)
                & (~is_read | (avail_t >= 1))
                & (~is_write | (free_c >= 1))
                & jnp.all((route_w & words_of(ic_busy > t)[:, None]) == 0, axis=0)
            )
            if ports is None:
                surv = cand
            else:
                # Port slots go to the highest-ranked timed candidates.
                chan_cand = cand & timed & jnp.any(c_oh, axis=1)
                same_c = jnp.any(c_oh[:, None, :] & c_oh[None, :, :], axis=2)
                rank = jnp.sum(
                    (lower_tri & chan_cand[None, :] & same_c).astype(jnp.int32),
                    axis=1, dtype=jnp.int32,
                )
                active_c = jnp.sum(jnp.where(c_oh, active[None], 0), axis=1, dtype=jnp.int32)
                surv = cand & (~chan_cand | (active_c + rank < ports))
            # A start is deferred (next round, same t) when a higher-
            # priority surviving timed candidate shares an interconnect.
            share = jnp.any((route_w[:, :, None] & route_w[:, None, :]) != 0, axis=0)
            blocked = jnp.any(lower_tri & (surv & timed)[None, :] & share, axis=1)
            win = surv & ~blocked

            # ---- apply: zero-duration effects (reads before writes),
            # then timed occupations — all disjoint.
            zd = win & ~timed
            rho = apply_reads(d["cs_mask"], zd & is_read, rho_adv, rho)
            omega, rho = apply_writes(c_oh, zd & is_write, omega, rho)
            cur, in_w, iters, owner = finish_windows(zd, cur, in_w, iters, owner)

            tw = win & timed
            running = running | tw
            busy = jnp.where(tw, t + dur_t, busy)
            ic_claim = tw[:, None] & route_t                       # (A,H)
            ic_busy = jnp.where(
                jnp.any(ic_claim, axis=0),
                jnp.sum(jnp.where(ic_claim, (t + dur_t)[:, None], 0), axis=0, dtype=jnp.int32),
                ic_busy,
            )
            active = active + jnp.sum(
                (tw[:, None] & c_oh).astype(jnp.int32), axis=0, dtype=jnp.int32
            )
            # Record the started tasks' descriptor fields for their
            # completion phase (only timed tasks with a channel matter;
            # the port decrement is gated by run_coh, zero when none).
            run_read = jnp.where(tw, is_read, run_read)
            run_write = jnp.where(tw, is_write, run_write)
            run_coh = jnp.where(tw[:, None], c_oh, run_coh)
            run_cs = jnp.where(tw[:, None, None], d["cs_mask"], run_cs)
            run_gc = jnp.where(tw, d["gamma_c"], run_gc)

            progressed = jnp.any(due | fire_win | win)
            # Early quiescence: a round whose winners were all timed and
            # whose candidates all won cannot have enabled anything new
            # at this instant (timed starts only consume resources; every
            # token/core effect this round fed the candidate computation
            # above), so the confirming round is skipped and time can
            # advance immediately.
            early = ~jnp.any(zd) & ~jnp.any(cand & ~win)
            state = (t, omega, rho, active, owner, ic_busy,
                     in_w, running, busy, cur, iters, fire,
                     run_read, run_write, run_coh, run_cs, run_gc)
            return state, progressed, early

        def cond(c):
            i, state, dead, done = c
            return (i < max_steps) & ~dead & ~done

        def body(c):
            i, state, _, _ = c
            # One synchronous round (model.py discipline); when the round
            # changes nothing the instant is quiescent, so the same
            # iteration checks termination and jumps time to the next task
            # completion — vmapped batch elements at different phases all
            # do useful work every iteration.
            state, progressed, early = round_fn(state)
            t, iters, running, busy = state[0], state[10], state[7], state[8]
            settled = ~progressed | early
            done = settled & jnp.all(iters >= K)
            dead = settled & ~done & ~jnp.any(running)
            next_t = jnp.min(jnp.where(running, busy, _I32_INF))
            t = jnp.where(settled & ~done & ~dead, next_t, t)
            state = (t,) + state[1:]
            return (i + 1, state, dead, done)

        # Every iteration applies ≥ 1 micro-transition, advances time past
        # a timed completion, or terminates.  A window is ≤ 1 + 2·n_tasks
        # transitions (fire, then start+completion per task) and every
        # time advance consumes ≥ 1 of the ≤ K·T timed completions, so
        # K·(3T + A) + slack bounds the trip count — never cuts short.
        max_steps = K * jnp.int32(3 * total_tasks + A + 2) + 8

        state = (
            jnp.int32(0),                        # t
            delay % gamma,                       # omega
            jnp.where(                           # rho (δ pre-loads views)
                reader_mask & (delay[:, None] > 0), 0, -1
            ).astype(jnp.int32),
            jnp.zeros(static["C"], jnp.int32),   # active timed accesses
            jnp.full(static["P"], -1, jnp.int32),  # core owner
            jnp.zeros(static["H"], jnp.int32),   # interconnect busy-until
            jnp.zeros(A, bool),                  # in_window
            jnp.zeros(A, bool),                  # running
            jnp.zeros(A, jnp.int32),             # busy_until
            jnp.zeros(A, jnp.int32),             # cur task
            jnp.zeros(A, jnp.int32),             # iterations fired
            jnp.full((A, int(k_max)), -1, jnp.int32),  # fire times
            jnp.zeros(A, bool),                  # running task: is_read
            jnp.zeros(A, bool),                  # running task: is_write
            jnp.zeros((A, C), bool),             # running task: chan one-hot
            jnp.zeros((A, C, R), bool),          # running task: (chan, slot)
            jnp.ones(A, jnp.int32),              # running task: γ(chan)
        )
        _, state, dead, _ = lax.while_loop(
            cond, body, (jnp.int32(0), state, jnp.bool_(False), jnp.bool_(False))
        )
        return state[11], dead, state[0]  # fire_times, deadlocked, horizon

    return simulate_one, tables


def _build_sim(static, cfg: SimConfig, k_max: int, donate: bool):
    import jax

    simulate_one, tables = build_simulate_one(static, cfg.mrb_ports, k_max)

    def batched(tb, core_oh, gamma, K):
        return jax.vmap(
            simulate_one, in_axes=(None, 0, 0, 0, None)
        )(tables, tb, core_oh, gamma, K)

    return jax.jit(batched, donate_argnums=(0, 1, 2) if donate else ())


def _get_compiled(
    static, key, cfg: SimConfig, k_max: int, backend: str, donate: bool
):
    donate = donate and backend != "pallas"  # pallas path never donates
    full_key = (key, backend, donate)
    fn = _COMPILED.get(full_key)
    if fn is None:
        _wire_fast_cpu()
        ensure_compile_cache()
        if backend == "pallas":
            from ..kernels.sim_step import build_pallas_sim

            fn = build_pallas_sim(static, cfg.mrb_ports, k_max)
        else:
            fn = _build_sim(static, cfg, k_max, donate)
        obs.counter_add("sim.cache_builds", backend=backend)
        _COMPILED[full_key] = fn
    return fn


# ---------------------------------------------------------------- wrappers
def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _run_batch(
    progs: Sequence[SimProgram],
    total_iters: int,
    cfg: SimConfig,
    backend: str,
    donate: bool,
):
    static, batched = _lower_batch(progs)
    B = len(progs)
    Bb = _bucket(B)
    arrs = [batched["tb"], batched["core_oh"], batched["gamma"]]
    if Bb > B:
        # Pad to the batch-size bucket with copies of element 0 so sub-batch
        # horizon-doubling reruns reuse a handful of compiled shapes.
        arrs = [np.concatenate([a] + [a[:1]] * (Bb - B)) for a in arrs]
    # The fire buffer is sized to the power-of-two bucket of the requested
    # firing count, not max_iterations: the per-round fire update touches
    # the whole buffer, so a tight buffer keeps rounds cheap while
    # horizon-doubling reruns still compile at most once per bucket.
    k_max = min(_bucket(max(2, total_iters)), cfg.max_iterations)
    key = (_structure_key(progs[0], cfg), Bb, k_max)
    fn = _get_compiled(static, key, cfg, k_max, backend, donate)
    with obs.span("sim.execute", backend=backend, B=B, Bb=Bb, k_max=int(k_max)):
        fire, dead, horizon = fn(*arrs, np.int32(total_iters))
    return (
        np.asarray(fire)[:B],
        np.asarray(dead)[:B],
        np.asarray(horizon)[:B],
    )


def batch_simulate(
    g: ApplicationGraph,
    arch: ArchitectureGraph,
    schedules: Sequence[Schedule],
    config: Optional[SimConfig] = None,
    *,
    backend: str = "vectorized",
    donate: bool = False,
) -> List[SimResult]:
    """Simulate a batch of phenotypes sharing one (graph, arch) pair.

    Returns one :class:`~repro.sim.events.SimResult` per schedule (no
    traces).  Each element follows the same horizon-doubling policy as
    ``events.simulate`` — it is measured at the first horizon in the
    sequence ``iterations, 2·iterations, …`` where its tail is periodic —
    so results are backend-identical.  ``backend`` selects the fused-scan
    lax implementation (``"vectorized"``) or the Pallas actor-step kernel
    (``"pallas"``, interpret mode off-TPU); ``donate=True`` donates the
    batched operand buffers to the compiled call (lax backend only — the
    Pallas route ignores it).
    """
    cfg = config or SimConfig()
    if backend not in BATCH_BACKENDS:
        raise ValueError(f"backend must be one of {BATCH_BACKENDS}")
    if not schedules:
        return []
    progs = [lower_phenotype(g, arch, s) for s in schedules]
    out: List[Optional[SimResult]] = [None] * len(progs)

    for i, pr in enumerate(progs):
        if predict_horizon(pr, cfg) > INT32_SAFE_HORIZON:
            from .events import simulate as ev_simulate

            obs.counter_add("sim.int32_fallbacks", phase="predicted")
            out[i] = ev_simulate(g, arch, pr.schedule, _no_trace(cfg))

    remaining = [i for i, r in enumerate(out) if r is None]
    iters = max(2, cfg.iterations)
    while remaining:
        sub = [progs[i] for i in remaining]
        fire, dead, horizon = _run_batch(sub, iters, cfg, backend, donate)
        still: List[int] = []
        at_cap = iters >= cfg.max_iterations
        for j, i in enumerate(remaining):
            # Post-check the int32 guard: the self-timed horizon can exceed
            # the analytic-period prediction (contention slows execution),
            # so a wrapped element is re-run on the exact events backend.
            if (
                int(horizon[j]) < 0
                or int(horizon[j]) >= INT32_SAFE_HORIZON
                or (fire[j] < -1).any()
            ):
                from .events import simulate as ev_simulate

                obs.counter_add("sim.int32_fallbacks", phase="wrapped")
                out[i] = ev_simulate(g, arch, progs[i].schedule, _no_trace(cfg))
                continue
            ft = {
                a: [int(x) for x in fire[j, ai, :iters] if x >= 0]
                for ai, a in enumerate(progs[i].actors)
            }
            if bool(dead[j]):
                out[i] = SimResult(
                    period=float("inf"), converged=False, deadlocked=True,
                    iterations=iters, horizon=int(horizon[j]), fire_times=ft,
                )
                continue
            period = measure_period(
                ft, max_multiplicity=cfg.max_multiplicity, checks=cfg.checks
            )
            if period is not None:
                out[i] = SimResult(
                    period=period, converged=True, deadlocked=False,
                    iterations=iters, horizon=int(horizon[j]), fire_times=ft,
                )
            elif at_cap:
                out[i] = SimResult(
                    period=fallback_period(ft), converged=False,
                    deadlocked=False, iterations=iters,
                    horizon=int(horizon[j]), fire_times=ft,
                )
            else:
                still.append(i)
        if still:
            obs.event(
                "sim.horizon_double", pending=len(still),
                next_iters=min(cfg.max_iterations, iters * 2),
            )
        remaining = still
        iters = min(cfg.max_iterations, iters * 2)
    return [r for r in out if r is not None]


def batch_simulate_periods(
    g: ApplicationGraph,
    arch: ArchitectureGraph,
    schedules: Sequence[Schedule],
    config: Optional[SimConfig] = None,
    *,
    backend: str = "vectorized",
    donate: bool = False,
) -> List[float]:
    """Measured steady-state period per phenotype (batched backend)."""
    return [
        r.period
        for r in batch_simulate(
            g, arch, schedules, config, backend=backend, donate=donate
        )
    ]


def _no_trace(cfg: SimConfig) -> SimConfig:
    from dataclasses import replace

    return replace(cfg, trace=False)
