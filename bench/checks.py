"""The comparison that decides a run's ``correct``.

After the window has closed, a sample drawn from the seed of what the
timed path produced (recorded by ``harness.Taps``) is recomputed by the
plain reference (``ref.search``) and compared.  Each number compared has
its limit in the traffic mix's ``limits``:

``objective_gap``       relaxed objective vectors the window produced
                        (fused generation survivors, per-pattern decodes)
                        against the reference decode + simulation: largest
                        |got − want| / max(|want|, 1)
``selection_mismatch``  share of rows the window's ranking, truncation and
                        variation chose that the reference replay of the
                        same step, from the same inputs and PRNG key, did not
``rank_mismatch``       rows whose front index differs from the host
                        NSGA-II ranking (exact path)
``crowd_gap``           crowding distances against the host NSGA-II's,
                        relative as above (exact path)
``schedule_gap``        memory, core cost and simulated period of
                        finished schedules (engine decodes in the window,
                        and the final front) against the same recomputed
                        from each schedule
``schedule_violations`` violations the independent verifier finds in those
                        schedules, each checked against its own period
``samples_short``       kinds of sample in the mix's ``sample`` that the
                        run did not fill: a tap that recorded nothing, or
                        a window too short for the sample

Every number the mix gives a limit must be computed: one that is missing
reads as infinitely far off.  Each kind in the mix's ``sample`` must yield
its full count (``front`` and ``setup_decode``, bounded by what the search
holds rather than by the window, at least one item).

With ``control`` the reference computed at the next lower precision
(float32 for the float64 the configuration states) takes the program's
place; it must come out not correct.
"""
from __future__ import annotations

import math
import os
import random
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from ref.search import Problem, host_rank_crowd, rel_gap, truncation, vary

UP_TO = ("front", "setup_decode")   # sample kinds that need only one item
BIG = 1.0e300   # stands for an infinite gap in the result line

_WORKER: Dict[str, Any] = {}


def _init(config, mix) -> None:
    _WORKER["prob"] = Problem(config["graph"], config["arch"], mix["objectives"],
                              mix["strategy"], config["pipelined"])


def _decode(row, dtype_name: str):
    return _WORKER["prob"].relaxed(row, getattr(np, dtype_name))


def _schedule(xi, sched, dtype_name: str):
    return _WORKER["prob"].check_schedule(xi, sched, getattr(np, dtype_name))


def snap(F: np.ndarray) -> np.ndarray:
    """Values to 12 significant digits: objective values that agree in
    exact arithmetic compare equal in the replayed ranking even when the
    device and the host round the last bit differently (distinct values
    of these objectives differ in the 7th digit or earlier)."""
    F = np.asarray(F, np.float64)
    out = F.copy()
    fin = np.isfinite(F)
    out[fin] = [float(f"{v:.12g}") for v in F[fin]]
    return out


def _rows_key(rows: np.ndarray) -> List[bytes]:
    return [np.asarray(r, np.int32).tobytes() for r in rows]


def _multiset_miss(got: np.ndarray, want: np.ndarray) -> int:
    """Rows of ``got`` not matched by a row of ``want`` (as multisets)."""
    pool: Dict[bytes, int] = {}
    for k in _rows_key(want):
        pool[k] = pool.get(k, 0) + 1
    miss = 0
    for k in _rows_key(got):
        if pool.get(k, 0):
            pool[k] -= 1
        else:
            miss += 1
    return miss


class _Ref:
    """Reference computations, the slow ones spread over worker processes
    that import only ``ref``."""

    def __init__(self, config, mix, pool) -> None:
        self.prob = Problem(config["graph"], config["arch"], mix["objectives"],
                            mix["strategy"], config["pipelined"])
        self.pool = pool

    def decode(self, rows: np.ndarray, dtype) -> np.ndarray:
        rows = [np.asarray(r).tolist() for r in rows]
        if not rows:
            return np.zeros((0, len(self.prob.objectives)))
        return np.array(list(self.pool.map(_decode, rows, [dtype.__name__] * len(rows))))

    def schedules(self, items, dtype) -> List[Tuple[np.ndarray, int]]:
        if not items:
            return []
        xis, scheds = zip(*items)
        return list(self.pool.map(_schedule, xis, scheds, [dtype.__name__] * len(items)))


class _Sampler:
    """Draws each kind's sample from the seed and notes the kinds that
    came short of what the mix asks."""

    def __init__(self, rng: random.Random, asked: Dict[str, int]) -> None:
        self.rng = rng
        self.asked = asked
        self.short: List[str] = []

    def __call__(self, kind: str, items: Sequence) -> List:
        k = self.asked.get(kind, 0)
        if not k:
            return []
        if len(items) < (1 if kind in UP_TO else k):
            self.short.append(kind)
        if len(items) <= k:
            return list(items)
        idx = sorted(self.rng.sample(range(len(items)), k))
        return [items[i] for i in idx]


def compare(config, mix, taps, run, seed: int, sizes: Dict[str, int], *,
            control: bool = False) -> Dict[str, Any]:
    """Numbers compared, each with its limit, and the verdict."""
    sample = _Sampler(random.Random(seed), mix["sample"])
    workers = max(1, min(12, (os.cpu_count() or 2) - 1))
    with ProcessPoolExecutor(workers, mp_context=get_context("spawn"),
                             initializer=_init, initargs=(config, mix)) as pool:
        ref = _Ref(config, mix, pool)
        got = _numbers(ref, taps, run, sample, control, mix["limits"], sizes)
    limits = dict(mix["limits"], samples_short=0)
    got["samples_short"] = (float(len(sample.short)), len(mix["sample"]), len(sample.short))
    numbers = {}
    correct = True
    for name, limit in limits.items():
        value, rows, _ = got.get(name, (math.inf, 0, 0))
        value = BIG if math.isinf(value) else value
        numbers[name] = {"value": value, "limit": limit, "checked": rows}
        correct &= value <= limit
    failed = sum(bad for _, _, bad in got.values())
    return dict(correct=bool(correct), failed=int(failed), numbers=numbers)


def _numbers(ref: _Ref, taps, run, sample: _Sampler, control: bool, limits, sizes):
    """``name -> (value, rows checked, rows outside the limit)``."""
    f64, f32 = np.float64, np.float32
    prob = ref.prob
    mu, count = sizes["population"], sizes["offspring"]
    out: Dict[str, Tuple[float, int, int]] = {}

    def add(name: str, value: float, rows: int, bad: int) -> None:
        v0, r0, b0 = out.get(name, (0.0, 0, 0))
        out[name] = (max(v0, value), r0 + rows, b0 + bad)

    by_label: Dict[str, list] = {}
    for label, args, res in taps.steps:
        by_label.setdefault(label, []).append((args, res))

    # Fused generation: vary -> decode + simulate -> rank -> truncate.
    for args, res in sample("gen", by_label.get("gen", [])):
        kv, genes, F = (np.asarray(a) for a in args)
        children = vary(kv, genes, F, prob, count, f64)
        cF = ref.decode(children, f64)
        mg = np.concatenate([genes, children])
        order = truncation(np.concatenate([snap(F), snap(cF)]), f64)[:mu]
        want_g = mg[order]
        if control:
            c_children = vary(kv, genes, F, prob, count, f32)
            c_cF = ref.decode(c_children, f32)
            c_mg = np.concatenate([genes, c_children])
            c_order = truncation(np.concatenate([snap(F), snap(c_cF)]), f32)[:mu]
            got_g, got_F = c_mg[c_order], np.concatenate([F, c_cF])[c_order]
        else:
            got_g, got_F = np.asarray(res[0]), np.asarray(res[1])
        miss = _multiset_miss(got_g, want_g)
        add("selection_mismatch", miss / mu, mu, miss)
        # Objective vectors of the surviving rows against the reference.
        # Parents carry their values over; children take the reference's.
        want_of = dict(zip(_rows_key(genes), F))
        want_of.update(zip(_rows_key(children), cF))
        rows = [(g, f) for g, f in zip(_rows_key(got_g), got_F) if g in want_of]
        gaps = [rel_gap(f, want_of[g]) for g, f in rows]
        add("objective_gap", max(gaps, default=0.0), len(rows),
            sum(x > limits["objective_gap"] for x in gaps))

    # Per-pattern decodes (ξ explored): a sample of the window's, and the
    # initial population's, whose random genotypes reach more of the space.
    decodes = sample("setup_decode", by_label.get("setup_decode", []))
    decodes += sample("decode", by_label.get("decode", []))
    for (genes,), res in decodes:
        genes = np.asarray(genes)
        want = ref.decode(genes, f64)
        got = ref.decode(genes, f32) if control else np.asarray(res)
        gaps = [rel_gap(a, b) for a, b in zip(got, want)]
        add("objective_gap", max(gaps, default=0.0), len(gaps),
            sum(x > limits["objective_gap"] for x in gaps))

    # Variation (ξ explored): same inputs and key, same children.
    for args, res in sample("vary", by_label.get("vary", [])):
        kv, genes, F = (np.asarray(a) for a in args)
        want = vary(kv, genes, F, prob, count, f64)
        got = vary(kv, genes, F, prob, count, f32) if control else np.asarray(res)
        bad = int((np.asarray(got) != want).any(axis=1).sum())
        add("selection_mismatch", bad / count, count, bad)

    # Elitist truncation (ξ explored): the same survivors.
    for (mF,), res in sample("rank", by_label.get("rank", [])):
        mF = np.asarray(mF)
        want = set(truncation(mF, f64)[:mu])
        got = set(truncation(mF, f32)[:mu]) if control else set(np.asarray(res)[:mu].tolist())
        bad = len(got - want)
        add("selection_mismatch", bad / mu, mu, bad)

    # Exact path: the device ranking against the host NSGA-II's.
    for objs, rank, crowd in sample("rank_crowd", taps.ranks):
        want_r, want_c = host_rank_crowd(objs, f64)
        if control:
            rank, crowd = host_rank_crowd(objs, f32)
        idx = sorted(want_r)
        bad = sum(rank.get(i) != want_r[i] for i in idx)
        add("rank_mismatch", float(bad), len(idx), bad)
        gaps = [rel_gap(crowd.get(i, math.nan), want_c[i]) for i in idx]
        add("crowd_gap", max(gaps, default=0.0), len(idx),
            sum(x > limits["crowd_gap"] for x in gaps))

    # Finished schedules: engine decodes in the window and the final front.
    # The period is the schedule's own; the verifier holds it to it.
    feasible = [i for batch in taps.batches for i in batch if i.feasible]
    inds = sample("window_schedules", feasible)
    inds += sample("front", [i for i in run.archive if i.feasible])
    items = [(tuple(i.genotype.xi), i.schedule.to_json()) for i in inds]
    want = ref.schedules(items, f64)
    names = [o for o in prob.objectives if o != "period"]
    if control:
        got_objs = [[o[k] for k in names] for o, _ in ref.schedules(items, f32)]
    else:
        got_objs = [[dict(zip(prob.objectives, i.objectives))[k] for k in names] for i in inds]
    gaps = [rel_gap(g, [w[k] for k in names]) for g, (w, _) in zip(got_objs, want)]
    viol = [v for _, v in want]
    add("schedule_gap", max(gaps, default=0.0), len(gaps),
        sum(x > limits["schedule_gap"] for x in gaps))
    add("schedule_violations", float(sum(viol)), len(viol), sum(v > 0 for v in viol))
    return out
