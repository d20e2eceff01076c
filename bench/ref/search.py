"""Plain reference of the search that ``jax_nsga2`` runs.

Written from the published semantics of the paper (arXiv 2311.17473,
Algorithms 1-4 and Eqs. 8-25) and of the repository's documented
relaxation, as straightforward Python loops over the configuration's JSON
form and the plain model in :mod:`.selftimed`:

* :meth:`Problem.relaxed` — the list-scheduling relaxation of one genotype:
  Algorithm 2's greedy channel binding with the declared capacities, one
  ASAP pass in arbitration order, the resource lower bound P_lb, the
  capacity estimate δ + ⌊(F − s_w)/P_lb⌋ + 1, and, for ``sim_period``, the
  self-timed execution of that phenotype for ``SIM_FIRINGS`` firings per
  actor.
* :func:`relaxed_ranks`, :func:`relaxed_crowding`, :func:`truncation` —
  NSGA-II non-dominated sorting, crowding distance and elitist order with
  ties broken by row, as the device ranking documents them.
* :func:`host_rank_crowd` — the host NSGA-II ranking (front discovery
  order, crowding per front), which the exact path must equal.
* :func:`vary` — the relaxed path's tournament, uniform crossover and
  resampling mutation, drawn from the same counter-based JAX PRNG stream.
* :meth:`Problem.check_schedule` — a finished schedule's memory, core cost
  and simulated period recomputed from its bindings and capacities, and
  its violations under the independent verifier (which checks the
  schedule against its own period).

``dtype`` selects the arithmetic of the floating-point results:
``np.float64`` is the reference, ``np.float32`` the control.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

from . import selftimed as st
from .architecture import ArchitectureGraph
from .graph import ApplicationGraph
from .schedule import Schedule
from .verifier import verify_schedule

DECISIONS = ("PROD", "TILE-PROD", "CONS", "TILE-CONS", "GLOBAL")
SIM_FIRINGS = 32          # firings per actor of the relaxed simulation
CROSSOVER_RATE = 0.95     # the explorer's whole-child crossover rate

__all__ = [
    "Problem",
    "relaxed_ranks",
    "relaxed_crowding",
    "truncation",
    "host_rank_crowd",
    "vary",
    "rel_gap",
]


class Problem:
    """One exploration deployment: graph, platform, objectives, strategy,
    each graph and platform in the configuration's JSON form."""

    def __init__(self, graph: dict, arch: dict, objectives: Sequence[str],
                 strategy: str, pipelined: bool = True) -> None:
        self.graph = graph
        self.arch = arch
        self.objectives = tuple(objectives)
        self.pipelined = pipelined
        self.mcast = sorted(st.multicast(graph))
        self.channels = sorted(graph["channels"])
        self.actors = sorted(graph["actors"])
        cores = arch["cores"]
        self.allowed = {
            a: [p for p in sorted(cores) if cores[p]["ctype"] in graph["actors"][a]["exec_times"]]
            for a in self.actors
        }
        self.n_xi, self.n_cd = len(self.mcast), len(self.channels)
        self.n_genes = self.n_xi + self.n_cd + len(self.actors)
        self.bounds = np.array(
            [2] * self.n_xi + [len(DECISIONS)] * self.n_cd
            + [len(self.allowed[a]) for a in self.actors], np.int32)
        # Reference pins ξ = 0, MRB_Always ξ = 1, MRB_Explore leaves it free.
        forced = {"Reference": 0, "MRB_Always": 1}.get(strategy)
        self.forced_mask = np.zeros(self.n_genes, bool)
        self.forced_vals = np.zeros(self.n_genes, np.int32)
        self.mut_mask = np.ones(self.n_genes, bool)
        if forced is not None and self.n_xi:
            self.forced_mask[: self.n_xi] = True
            self.forced_vals[: self.n_xi] = forced
        if forced is not None:
            self.mut_mask[: self.n_xi] = False
        self._graphs: Dict[Tuple[int, ...], dict] = {}
        self._arch = None       # the verifier's form of the platform

    # ------------------------------------------------------------ graphs
    def transformed(self, xi: Tuple[int, ...]) -> dict:
        """Algorithm 1 for one ξ pattern, then the §VI pipeline delays."""
        gt = self._graphs.get(xi)
        if gt is None:
            gt = st.substitute(self.graph, dict(zip(self.mcast, xi)))
            if self.pipelined:
                gt = st.pipelined(gt)
            self._graphs[xi] = gt
        return gt

    def _place(self, decision: str, core: str) -> str:
        if decision in ("PROD", "CONS"):
            return f"q_{core}"
        if decision in ("TILE-PROD", "TILE-CONS"):
            return f"q_{self.arch['cores'][core]['tile']}"
        return self.arch["global_memory"]

    def _core_cost(self, cores) -> float:
        arch = self.arch
        return float(sum(arch["core_costs"].get(arch["cores"][p]["ctype"], 1.0)
                         for p in set(cores)))

    # ----------------------------------------------------- relaxed decode
    def relaxed(self, row: Sequence[int], dtype=np.float64) -> np.ndarray:
        """Relaxed objective vector of one gene row ``[ξ | C_d | β_A]``."""
        gt, core, mem, p_lb, gamma = self.phenotype(row)
        chans = gt["channels"]
        vals = {
            "period": float(p_lb),
            "memory": float(sum(gamma[c] * ch["token_bytes"] for c, ch in chans.items())),
            "core_cost": self._core_cost(core.values()),
        }
        if "sim_period" in self.objectives:
            vals["sim_period"] = float(st.period_after(gt, self.arch, core, mem, gamma, SIM_FIRINGS))
        return np.array([dtype(vals[o]) for o in self.objectives], np.float64)

    def phenotype(self, row: Sequence[int]):
        """(graph, core of each actor, memory of each channel, P_lb, γ̂)
        of one gene row under the relaxation."""
        row = [int(v) for v in row]
        xi = tuple(row[: self.n_xi])
        cd = row[self.n_xi: self.n_xi + self.n_cd]
        ba = row[self.n_xi + self.n_cd:]
        gt = self.transformed(xi)
        chans = gt["channels"]
        arch = self.arch
        core = {
            a: self.allowed[a][ba[i] % len(self.allowed[a])]
            for i, a in enumerate(self.actors) if a in gt["actors"]
        }
        cpos = {c: i for i, c in enumerate(self.channels)}

        # Algorithm 2: channels in sorted order, declared capacities, each
        # decision falling back PROD → TILE-PROD → GLOBAL (CONS likewise),
        # TILE-* and GLOBAL straight to the global memory.  A multi-reader
        # buffer takes the decision of its first member channel.
        usage = {q: 0 for q in arch["memories"]}
        mem: Dict[str, str] = {}
        for c in sorted(chans):
            ch = chans[c]
            d = cd[cpos[ch["members"][0] if "members" in ch else c]]
            p = core[ch["src"]] if d < 2 else core[ch["dsts"][0]]
            chain = [self._place(DECISIONS[d], p)]
            chain.append(self._place(DECISIONS[d + 1], p) if d in (0, 2)
                         else arch["global_memory"])
            chain.append(arch["global_memory"])
            need = ch["capacity"] * ch["token_bytes"]
            q = chain[2]
            for cand in chain[:2]:
                if usage[cand] + need <= arch["memories"][cand]["capacity"]:
                    q = cand
                    break
            usage[q] += need
            mem[c] = q

        tasks = st.firing(gt, arch, core, mem)

        # One ASAP pass in arbitration order; an actor's window waits only
        # for zero-delay inputs written earlier in the same iteration.
        wfin = {c: 0 for c in chans}
        rfin: Dict[str, int] = {}
        wstart: Dict[str, int] = {}
        core_load = {p: 0 for p in arch["cores"]}
        link_load = {h: 0 for h in arch["interconnects"]}
        for a in st.arbitration_order(gt):
            t = max([wfin[c] for kind, c, _, _ in tasks[a]
                     if kind == st.READ and chans[c]["delay"] == 0] or [0])
            w_s: Dict[str, int] = {}
            w_f: Dict[str, int] = {}
            for kind, c, dur, links in tasks[a]:
                start, t = t, t + dur
                core_load[core[a]] += dur
                for h in links:
                    link_load[h] += dur
                if kind == st.READ:
                    rfin[c] = max(rfin.get(c, t), t)
                elif kind == st.WRITE:
                    w_s[c] = max(w_s.get(c, start), start)
                    w_f[c] = max(w_f.get(c, t), t)
            wstart.update(w_s)
            wfin.update(w_f)
        p_lb = max(1, max(core_load.values()), max(link_load.values(), default=0))

        gamma = {}
        for c, ch in chans.items():
            g = ch["capacity"]
            if c in rfin and c in wstart:
                g = max(g, ch["delay"] + (rfin[c] - wstart[c]) // p_lb + 1)
            gamma[c] = max(g, 1)

        return gt, core, mem, p_lb, gamma

    # ------------------------------------------------- finished schedules
    def check_schedule(self, xi: Tuple[int, ...], sched: dict,
                       dtype=np.float64) -> Tuple[Dict[str, float], int]:
        """(objectives other than the period recomputed from the schedule,
        verifier violations) for one finished schedule in its JSON form.
        The period is the schedule's own, which the verifier checks it
        against."""
        gt = self.transformed(tuple(xi))
        chans = gt["channels"]
        core, mem = sched["actor_binding"], sched["channel_binding"]
        cap = {c: sched["capacities"].get(c, ch["capacity"]) for c, ch in chans.items()}
        vals = {
            "memory": float(sum(cap[c] * ch["token_bytes"] for c, ch in chans.items())),
            "core_cost": self._core_cost(core.values()),
        }
        if "sim_period" in self.objectives:
            vals["sim_period"] = float(st.simulated_period(gt, self.arch, core, mem, cap))
        if self._arch is None:
            self._arch = ArchitectureGraph.from_dict(self.arch)
        report = verify_schedule(ApplicationGraph.from_dict(gt), self._arch,
                                 Schedule.from_json(sched))
        return {o: float(dtype(v)) for o, v in vals.items()}, len(report.violations)


# ------------------------------------------------------------------ ranking
def _dominates(F: np.ndarray) -> np.ndarray:
    le = np.all(F[:, None, :] <= F[None, :, :], axis=-1)
    lt = np.any(F[:, None, :] < F[None, :, :], axis=-1)
    return le & lt


def relaxed_ranks(F: np.ndarray) -> np.ndarray:
    """Front index per row: front r is every unranked row that no other
    unranked row dominates."""
    dom = _dominates(F)
    rank = np.full(len(F), -1)
    r = 0
    while (rank < 0).any():
        rem = rank < 0
        front = rem & ~(dom & rem[:, None]).any(axis=0)
        rank[front] = r
        r += 1
    return rank


def _crowd_front(F: np.ndarray, idx: List[int], dtype) -> Dict[int, float]:
    """NSGA-II crowding of one front; ``idx`` gives the tie order."""
    d = {i: dtype(0.0) for i in idx}
    inf = dtype(math.inf)
    for k in range(F.shape[1]):
        order = sorted(idx, key=lambda i: F[i, k])
        lo, hi = dtype(F[order[0], k]), dtype(F[order[-1], k])
        d[order[0]] = d[order[-1]] = inf
        if hi == lo:
            continue
        span = hi - lo
        for a, i in enumerate(order[1:-1], start=1):
            gap = dtype(F[order[a + 1], k]) - dtype(F[order[a - 1], k])
            if math.isinf(span):
                if math.isinf(gap):
                    d[i] = inf
                continue
            d[i] = dtype(d[i] + gap / span)
    return d


def relaxed_crowding(F: np.ndarray, ranks: np.ndarray, dtype=np.float64) -> np.ndarray:
    """Crowding distance of every row within its front, ties by row."""
    out = np.zeros(len(F), np.float64)
    for r in np.unique(ranks):
        front = [int(i) for i in np.nonzero(ranks == r)[0]]
        for i, v in _crowd_front(F, front, dtype).items():
            out[i] = v
    return out


def truncation(F: np.ndarray, dtype=np.float64) -> List[int]:
    """Elitist order: by (rank, −crowding), ties by row."""
    ranks = relaxed_ranks(F)
    crowd = relaxed_crowding(F, ranks, dtype)
    return sorted(range(len(F)), key=lambda i: (ranks[i], -crowd[i], i))


def host_rank_crowd(objs: Sequence[Sequence[float]], dtype=np.float64):
    """The host NSGA-II ``rank_crowd``: fronts in discovery order (each
    dominator's dominated rows in ascending order), crowding per front."""
    F = np.asarray(objs, np.float64)
    n = len(F)
    dom = _dominates(F)
    counts = dom.sum(axis=0)
    fronts = [[i for i in range(n) if counts[i] == 0]]
    while fronts[-1]:
        nxt = []
        for i in fronts[-1]:
            for j in np.nonzero(dom[i])[0]:
                counts[j] -= 1
                if counts[j] == 0:
                    nxt.append(int(j))
        fronts.append(nxt)
    rank: Dict[int, int] = {}
    crowd: Dict[int, float] = {}
    for fi, front in enumerate(f for f in fronts if f):
        for i, v in _crowd_front(F, front, dtype).items():
            rank[i] = fi
            crowd[i] = float(v)
    return rank, crowd


# ---------------------------------------------------------------- variation
def vary(key, genes: np.ndarray, F: np.ndarray, prob: Problem, count: int,
         dtype=np.float64) -> np.ndarray:
    """Children of one generation from the parents' ranks and crowding:
    binary tournaments on (rank, −crowding) keeping the first draw on a
    tie, whole-child uniform crossover, per-gene resampling at rate 1/G,
    strategy-forced genes pinned.  The PRNG stream is JAX's threefry in
    64-bit mode, split as the explorer splits it."""
    import jax
    import jax.numpy as jnp
    import jax.random as jr

    ranks = relaxed_ranks(F)
    crowd = relaxed_crowding(F, ranks, dtype)
    with jax.enable_x64(True):
        key = jnp.asarray(key)
        k1, k2, k3, k4 = jr.split(key, 4)

        def pick(k):
            ij = np.asarray(jr.randint(k, (2, count), 0, len(genes)))
            i, j = ij[0], ij[1]
            better = (ranks[i] < ranks[j]) | ((ranks[i] == ranks[j]) & (crowd[i] >= crowd[j]))
            return np.where(better, i, j)

        pa, pb = genes[pick(k1)], genes[pick(k2)]
        n, g = pa.shape
        k_gate, k_mix = jr.split(k3)
        do_cx = np.asarray(jr.uniform(k_gate, (n, 1))) < CROSSOVER_RATE
        take_a = np.asarray(jr.uniform(k_mix, (n, g))) < 0.5
        child = np.where(do_cx, np.where(take_a, pa, pb), pa)
        k_hit, k_val = jr.split(k4)
        hit = (np.asarray(jr.uniform(k_hit, (n, g))) < 1.0 / g) & prob.mut_mask[None, :]
        u = np.asarray(jr.uniform(k_val, (n, g)))
        new = np.minimum(np.floor(u * prob.bounds[None, :]).astype(np.int32),
                         prob.bounds[None, :] - 1)
        child = np.where(hit, new, child)
    return np.where(prob.forced_mask[None, :], prob.forced_vals[None, :], child).astype(np.int32)


# ---------------------------------------------------------------- compare
def rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |got − want| / max(|want|, 1); equal infinities count 0, an
    infinity on one side only counts ``inf``."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.size == 0:
        return 0.0
    same = (got == want)
    finite = np.isfinite(got) & np.isfinite(want)
    with np.errstate(invalid="ignore"):
        gap = np.where(finite, np.abs(got - want) / np.maximum(np.abs(want), 1.0), np.inf)
    return float(np.max(np.where(same, 0.0, gap)))
