"""CAPS-HMS — Communication-Aware Periodic Scheduling on Heterogeneous
Many-core Systems (paper Algorithm 5) and the heuristic decoder wrapped
around it (paper Algorithm 4).

The heuristic greedily places each ready actor (priority = topological
order) at the earliest start s'_a ∈ [s_a, s_a + P) such that
  * the bound core is free for the whole window  τ'_a = τ_EI + τ_a + τ_EO
    (reads packed directly before the execution, writes directly after), and
  * every interconnect traversed by each read/write is free during that
    task's slot,
wrapping occupancy into [0, P) via f_wrap.  On failure for every candidate
start, the decoder retries with P+1 (paper-faithful linear period search).

Efficiency notes (beyond-paper, semantics-preserving): instead of probing
every integer s'_a the search jumps to the end of the blocking busy
interval, which visits exactly the same sequence of *feasible* candidates
the paper's loop would accept, in O(#busy intervals) instead of O(P).
What does not depend on s'_a (the window's length, each communication
task's offset, duration and route) is laid out once per binding, so a
candidate costs one allocation-free ``UtilizationSet.busy_end`` query per
resource; a commit coalesces each new interval with its neighbours only,
and the ready queue is a heap.
"""
from __future__ import annotations

import heapq
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Set, Tuple

from .. import obs
from .architecture import ArchitectureGraph
from .binding import determine_channel_bindings
from .graph import ApplicationGraph, topological_priorities
from .schedule import (
    Schedule,
    TaskTimes,
    UtilizationSet,
    attach_binding,
    comm_times,
    f_wrap,
    period_lower_bound,
    required_capacities,
)

__all__ = ["caps_hms", "decode_via_heuristic", "DecodeResult"]


@dataclass
class DecodeResult:
    """Phenotype (P, β, γ) plus the full task timing for inspection.

    ``period`` is ``math.inf`` for infeasible decodes so that ad-hoc
    consumers comparing periods never rank an infeasible phenotype as
    "better" (the historical ``-1`` sentinel silently did exactly that);
    this matches the all-∞ objective vector at the ``EvalContext``
    boundary (``infeasible_objectives``).
    """

    schedule: Optional[Schedule]
    feasible: bool
    periods_tried: int = 0

    @property
    def period(self) -> float:
        return self.schedule.period if self.schedule else math.inf

    def to_json(self) -> Dict:
        """JSON form; infeasible results serialize with ``schedule: null``
        so ``period`` is ``math.inf`` again after ``from_json`` (the inf
        never has to survive JSON itself)."""
        return {
            "schedule": self.schedule.to_json() if self.schedule else None,
            "feasible": self.feasible,
            "periods_tried": self.periods_tried,
        }

    @classmethod
    def from_json(cls, d: Dict) -> "DecodeResult":
        sched = d.get("schedule")
        return cls(
            schedule=Schedule.from_json(sched) if sched else None,
            feasible=bool(d["feasible"]),
            periods_tried=d.get("periods_tried", 0),
        )


def _advance_past(period: int, s_abs: int, offset: int, busy_end: int) -> int:
    """Smallest s' > s_abs such that phase(s' + offset) == busy_end, i.e. the
    conflicting piece starting at phase((s_abs + offset) mod P) is moved to
    begin exactly at the end of the blocking busy interval."""
    phase = (s_abs + offset) % period
    delta = (busy_end - phase) % period
    return s_abs + (delta if delta > 0 else period)


@dataclass(frozen=True)
class _ActorLayout:
    """One actor's window under a binding, relative to its window start s:
    reads packed from 0, the execution at ``t_in``, writes after it."""

    core: str
    t_in: int
    t_win: int  # τ'_a = τ_EI + τ_a + τ_EO
    comms: Tuple[Tuple[int, int, Tuple[str, ...]], ...]  # (offset, τ, hops), τ > 0
    reads: Tuple[Tuple[Tuple[str, str], int], ...]  # ((c, a), offset)
    writes: Tuple[Tuple[Tuple[str, str], int], ...]  # ((a, c), offset)


@dataclass
class _Ctx:
    """Per-(binding, decisions) invariants hoisted out of the period search."""

    read_tau: Dict[Tuple[str, str], int]
    write_tau: Dict[Tuple[str, str], int]
    prio: Dict[str, int]
    layout: Dict[str, _ActorLayout]
    # Zero-delay dependencies, the only ones the placement order follows.
    preds0: Dict[str, Tuple[str, ...]]  # producers of a's zero-delay inputs
    succs0: Dict[str, Tuple[str, ...]]  # consumers of a's zero-delay outputs


def _build_ctx(
    g: ApplicationGraph,
    arch: ArchitectureGraph,
    actor_binding: Dict[str, str],
    channel_binding: Dict[str, str],
) -> _Ctx:
    attach_binding(g, channel_binding)
    read_tau, write_tau = comm_times(g, arch, actor_binding, channel_binding)
    layout: Dict[str, _ActorLayout] = {}
    preds0: Dict[str, Tuple[str, ...]] = {}
    succs0: Dict[str, Tuple[str, ...]] = {}
    for a in g.actors:
        core = actor_binding[a]
        comms: List[Tuple[int, int, Tuple[str, ...]]] = []
        reads: List[Tuple[Tuple[str, str], int]] = []
        writes: List[Tuple[Tuple[str, str], int]] = []
        off = 0
        for c in g.in_channels(a):
            tau = read_tau[(c, a)]
            reads.append(((c, a), off))
            if tau > 0:
                hops = arch.route_interconnects(core, channel_binding[c])
                comms.append((off, tau, tuple(hops)))
            off += tau
        t_in = off
        off += g.actors[a].exec_times[arch.cores[core].ctype]
        for c in g.out_channels(a):
            tau = write_tau[(a, c)]
            writes.append(((a, c), off))
            if tau > 0:
                hops = arch.route_interconnects(core, channel_binding[c])
                comms.append((off, tau, tuple(hops)))
            off += tau
        layout[a] = _ActorLayout(core, t_in, off, tuple(comms), tuple(reads), tuple(writes))
        preds0[a] = tuple(
            g.producer[c] for c in g.in_channels(a) if g.channels[c].delay == 0
        )
        succs0[a] = tuple(
            r
            for c in g.out_channels(a)
            if g.channels[c].delay == 0
            for r in g.consumers[c]
        )
    return _Ctx(read_tau, write_tau, topological_priorities(g), layout, preds0, succs0)


def caps_hms(
    g: ApplicationGraph,
    arch: ArchitectureGraph,
    actor_binding: Dict[str, str],
    channel_binding: Dict[str, str],
    period: int,
    ctx: Optional[_Ctx] = None,
) -> Optional[TaskTimes]:
    """Algorithm 5.  Returns task start times on success, None on failure."""
    if ctx is None:
        ctx = _build_ctx(g, arch, actor_binding, channel_binding)
    times, candidates = _place(g, arch, period, ctx)
    obs.counter_add("caps_hms.candidates", candidates)
    return times


def _place(
    g: ApplicationGraph, arch: ArchitectureGraph, period: int, ctx: _Ctx
) -> Tuple[Optional[TaskTimes], int]:
    """One CAPS-HMS attempt at ``period``: (task times or None, number of
    candidate starts tested)."""
    prio, layout, preds0, succs0 = ctx.prio, ctx.layout, ctx.preds0, ctx.succs0
    util: Dict[str, UtilizationSet] = {r: UtilizationSet() for r in arch.schedulable_resources()}
    times = TaskTimes()
    actor_start, read_start, write_start = times.actor_start, times.read_start, times.write_start
    s_min: Dict[str, int] = {a: 0 for a in g.actors}  # earliest start (deps)
    candidates = 0

    # Ready actors, popped by highest priority then name.
    ready = [(-prio[a], a) for a in g.actors if not preds0[a]]
    heapq.heapify(ready)
    queued: Set[str] = {a for _, a in ready}
    scheduled: Set[str] = set()

    while ready:
        a = heapq.heappop(ready)[1]
        lay = layout[a]
        t_win, comms = lay.t_win, lay.comms
        if t_win > period:
            return None, candidates  # cannot fit even alone
        core_util = util[lay.core]

        s = s_min[a]
        limit = s + period
        while s < limit:
            candidates += 1
            # Core window free?
            busy = core_util.busy_end(period, s, t_win)
            if busy >= 0:
                s = _advance_past(period, s, 0, busy)
                continue
            # Interconnects free for each comm task at its packed offset?
            jump = -1
            for o, tau, hops in comms:
                for h in hops:
                    busy = util[h].busy_end(period, s + o, tau)
                    if busy >= 0:
                        jump = _advance_past(period, s, o, busy)
                        break
                if jump >= 0:
                    break
            if jump < 0:
                break
            s = jump
        else:
            return None, candidates

        # Commit (Lines 17-21).
        core_util.add(f_wrap(period, s, t_win))
        for o, tau, hops in comms:
            pieces = f_wrap(period, s + o, tau)
            for h in hops:
                util[h].add(pieces)
        actor_start[a] = s + lay.t_in
        # Comm starts (reads then writes, packed; zero-time comms get the
        # packed position too for capacity accounting).
        for t, o in lay.reads:
            read_start[t] = s + o
        for t, o in lay.writes:
            write_start[t] = s + o
        scheduled.add(a)
        end = s + t_win
        for a2 in succs0[a]:
            if a2 in scheduled:
                continue
            if s_min[a2] < end:
                s_min[a2] = end
            if a2 not in queued and all(p in scheduled for p in preds0[a2]):
                queued.add(a2)
                heapq.heappush(ready, (-prio[a2], a2))

    if len(scheduled) != len(g.actors):
        # Unreachable actors (cyclic zero-delay parts) — treat as failure.
        return None, candidates

    # Cross-iteration dependency guard (Eq. 16 for δ ≥ 1 channels).  The
    # paper's Line 20 only propagates zero-delay dependencies; with initial
    # tokens a consumer of higher priority can be placed more than δ
    # periods before its producer's write completes.  Rejecting here makes
    # the decoder retry with a larger P, which absorbs the drift.
    write_tau = ctx.write_tau
    for c in g.channels:
        prod = g.producer[c]
        s_w = write_start[(prod, c)]
        tau_w = write_tau[(prod, c)]
        delta = g.channels[c].delay
        for r in g.consumers[c]:
            if s_w + tau_w - period * delta > read_start[(c, r)]:
                return None, candidates
    return times, candidates


def _search_period(
    g: ApplicationGraph,
    arch: ArchitectureGraph,
    actor_binding: Dict[str, str],
    beta_c: Dict[str, str],
    lb: int,
    cap: int,
    mode: str,
    ctx: _Ctx,
) -> Tuple[Optional[TaskTimes], int, int]:
    """Find the smallest period in [lb, cap] CAPS-HMS can schedule.

    mode='linear' is the paper's P ← P+1 loop.  mode='gallop' (default) is a
    semantics-preserving accelerant: multiplicative ramp to the first
    feasible P, then binary search down (feasibility of the greedy heuristic
    is monotone in P for all observed instances; the found period is re-
    verified by an actual schedule, so correctness never depends on this).
    Returns (times, period, attempts)."""
    tried = 0

    def attempt(P: int) -> Optional[TaskTimes]:
        nonlocal tried
        tried += 1
        return caps_hms(g, arch, actor_binding, beta_c, P, ctx)

    if mode == "linear":
        period = lb
        while period <= cap:
            t = attempt(period)
            if t is not None:
                return t, period, tried
            period += 1
        return None, -1, tried

    # gallop up
    lo_fail = lb - 1
    period = lb
    best: Optional[Tuple[TaskTimes, int]] = None
    while period <= cap:
        t = attempt(period)
        if t is not None:
            best = (t, period)
            break
        lo_fail = period
        period = max(period + 1, int(period * 1.25))
    if best is None:
        return None, -1, tried
    # binary search down between last failure and the success
    hi_t, hi_p = best
    lo = lo_fail
    while hi_p - lo > 1:
        mid = (lo + hi_p) // 2
        t = attempt(mid)
        if t is not None:
            hi_t, hi_p = t, mid
        else:
            lo = mid
    return hi_t, hi_p, tried


def decode_via_heuristic(
    g: ApplicationGraph,
    arch: ArchitectureGraph,
    decisions: Dict[str, str],
    actor_binding: Dict[str, str],
    *,
    max_period: Optional[int] = None,
    max_rebind_rounds: int = 8,
    period_search: str = "gallop",
) -> DecodeResult:
    """Algorithm 4: channel bindings → period search via CAPS-HMS → capacity
    enlargement → re-binding loop until all channels fit their memories."""
    capacities: Dict[str, int] = {c: ch.capacity for c, ch in g.channels.items()}
    beta_c = determine_channel_bindings(g, arch, decisions, capacities, actor_binding)
    tried = 0

    for _ in range(max_rebind_rounds):
        ctx = _build_ctx(g, arch, actor_binding, beta_c)
        read_tau, write_tau = ctx.read_tau, ctx.write_tau
        lb = period_lower_bound(g, arch, actor_binding, read_tau, write_tau)
        cap = max_period or (lb * 8 + 4096)
        times, period, n = _search_period(
            g, arch, actor_binding, beta_c, lb, cap, period_search, ctx
        )
        tried += n
        obs.counter_add("caps_hms.attempts", n)
        if times is None:
            return DecodeResult(None, False, tried)

        new_caps = required_capacities(g, times, period, read_tau)
        # Does everything still fit where it is bound?
        usage: Dict[str, int] = {}
        for c, gcap in new_caps.items():
            q = beta_c[c]
            usage[q] = usage.get(q, 0) + gcap * g.channels[c].token_bytes
        overflow = [
            q for q, used in usage.items() if used > arch.memories[q].capacity
        ]
        if not overflow:
            sched = Schedule(period, times, dict(actor_binding), beta_c, new_caps)
            return DecodeResult(sched, True, tried)
        beta_c = determine_channel_bindings(g, arch, decisions, new_caps, actor_binding)
    return DecodeResult(None, False, tried)
