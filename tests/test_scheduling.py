"""Scheduling: the paper's worked examples (Figs. 4, 5, 7), validity
invariants for both decoders on random graphs, stored CAPS-HMS decodes of
the paper's applications, and the utilization-set bookkeeping."""
import bisect
import hashlib
import json
import os
import random

import pytest
# hypothesis is a declared dev dependency (requirements-dev.txt); where it
# is absent the proptest driver runs the same properties deterministically.
from repro.scenarios.proptest import given, settings, st

from repro.core import apps, paper_architecture
from repro.core.architecture import ArchitectureGraph
from repro.core.caps_hms import decode_via_heuristic
from repro.core.dse import GenotypeSpace, evaluate_genotype, xi_mode
from repro.core.graph import ApplicationGraph
from repro.core.ilp import decode_via_ilp
from repro.core.mrb import substitute_mrbs
from repro.core.schedule import UtilizationSet, f_wrap, validate_schedule


def fig1_graph() -> ApplicationGraph:
    g = ApplicationGraph("fig1")
    et = lambda w: {"t1": w}
    g.add_actor("a1", et(1))
    g.add_actor("a2", et(1), multicast=True)
    g.add_actor("a3", et(7))
    g.add_actor("a4", et(7))
    g.add_actor("a5", et(1))
    g.add_channel("c1", "a1", "a2", delay=1, capacity=2, token_bytes=38000)
    g.add_channel("c2", "a2", "a3", capacity=2, token_bytes=38000)
    g.add_channel("c3", "a2", "a4", capacity=2, token_bytes=38000)
    g.add_channel("c4", "a3", "a5", capacity=2, token_bytes=38000)
    g.add_channel("c5", "a4", "a5", capacity=2, token_bytes=38000)
    return g


def one_tile_arch(n_cores=6, bw=38000) -> ArchitectureGraph:
    a = ArchitectureGraph("t1")
    a.add_tile(
        "T1", ["t1"] * n_cores,
        core_local_capacity=2_500_000, tile_local_capacity=50_000_000,
        crossbar_bandwidth=bw,
    )
    a.set_global(1 << 60, bw // 2)
    a.set_core_costs({"t1": 1.0})
    return a


P1, P2, P3, P4 = "p_T1_1", "p_T1_2", "p_T1_3", "p_T1_4"


class TestPaperTraces:
    def test_fig5_period_7_multicast_retained(self):
        g, arch = fig1_graph(), one_tile_arch()
        ba = {"a1": P3, "a2": P3, "a5": P3, "a3": P1, "a4": P2}
        cd = {"c1": "PROD", "c2": "CONS", "c3": "CONS", "c4": "PROD", "c5": "PROD"}
        res = decode_via_heuristic(g, arch, cd, ba)
        assert res.feasible and res.period == 7
        assert validate_schedule(g, arch, res.schedule) == []

    def test_fig4_period_8_with_mrb(self):
        g, arch = fig1_graph(), one_tile_arch()
        gt = substitute_mrbs(g, {"a2": 1})
        mrb = next(c for c in gt.channels if c.startswith("mrb"))
        assert gt.channels[mrb].capacity == 4  # γ = γ_in + γ_out (Fig. 2)
        assert gt.channels[mrb].delay == 1
        ba = {"a1": P3, "a5": P3, "a3": P1, "a4": P2}
        cd = {mrb: "PROD", "c4": "PROD", "c5": "PROD"}
        res = decode_via_heuristic(gt, arch, cd, ba)
        assert res.feasible and res.period == 8
        assert validate_schedule(gt, arch, res.schedule) == []

    def test_exact_decoder_matches_figs(self):
        g, arch = fig1_graph(), one_tile_arch()
        ba = {"a1": P3, "a2": P3, "a5": P3, "a3": P1, "a4": P2}
        cd = {"c1": "PROD", "c2": "CONS", "c3": "CONS", "c4": "PROD", "c5": "PROD"}
        res = decode_via_ilp(g, arch, cd, ba)
        assert res.feasible and res.period == 7 and res.proven_optimal
        gt = substitute_mrbs(g, {"a2": 1})
        mrb = next(c for c in gt.channels if c.startswith("mrb"))
        res = decode_via_ilp(gt, arch, {mrb: "PROD", "c4": "PROD", "c5": "PROD"},
                             {"a1": P3, "a5": P3, "a3": P1, "a4": P2})
        assert res.feasible and res.period == 8 and res.proven_optimal

    def test_fig7_period_10_crossbar_bound(self):
        """Fig. 7: all channels on the tile memory, every comm 1 unit; the
        crossbar carries 10 comm tasks ⇒ P = 10."""
        g = ApplicationGraph("fig7")
        et = lambda w: {"t1": w}
        g.add_actor("a1", et(2)); g.add_actor("a2", et(1), multicast=True)
        g.add_actor("a3", et(3)); g.add_actor("a4", et(3)); g.add_actor("a5", et(2))
        g.add_channel("c1", "a1", "a2", delay=1, capacity=2, token_bytes=38000)
        g.add_channel("c2", "a2", "a3", capacity=2, token_bytes=38000)
        g.add_channel("c3", "a2", "a4", capacity=2, token_bytes=38000)
        g.add_channel("c4", "a3", "a5", capacity=2, token_bytes=38000)
        g.add_channel("c5", "a4", "a5", capacity=2, token_bytes=38000)
        arch = one_tile_arch()
        ba = {"a1": P1, "a2": P1, "a3": P2, "a4": P3, "a5": P4}
        cd = {c: "TILE-PROD" for c in g.channels}
        res = decode_via_heuristic(g, arch, cd, ba)
        assert res.feasible and res.period == 10
        assert validate_schedule(g, arch, res.schedule) == []


def random_graph(rng: random.Random, n_actors: int) -> ApplicationGraph:
    g = ApplicationGraph("rand")
    for i in range(n_actors):
        w = rng.randint(1, 9)
        g.add_actor(f"a{i}", {"t1": w})
    ci = 0
    for i in range(1, n_actors):
        src = f"a{rng.randrange(i)}"
        g.add_channel(
            f"c{ci}", src, f"a{i}",
            delay=rng.randint(0, 1), capacity=rng.randint(1, 3),
            token_bytes=rng.choice([0, 19000, 38000, 76000]),
        )
        ci += 1
    return g


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 9))
def test_caps_hms_schedules_are_valid(seed, n):
    """Property: any random DAG + random binding decodes into a schedule
    satisfying every paper feasibility condition (Eqs. 16-23)."""
    rng = random.Random(seed)
    g = random_graph(rng, n)
    arch = one_tile_arch()
    cores = sorted(arch.cores)
    ba = {a: rng.choice(cores) for a in g.actors}
    from repro.core.binding import CHANNEL_DECISIONS

    cd = {c: rng.choice(CHANNEL_DECISIONS) for c in g.channels}
    res = decode_via_heuristic(g, arch, cd, ba)
    assert res.feasible
    assert validate_schedule(g, arch, res.schedule) == []


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 5_000), n=st.integers(2, 6))
def test_exact_never_worse_than_heuristic(seed, n):
    rng = random.Random(seed)
    g = random_graph(rng, n)
    arch = one_tile_arch(n_cores=3)
    cores = sorted(arch.cores)
    ba = {a: rng.choice(cores) for a in g.actors}
    from repro.core.binding import CHANNEL_DECISIONS

    cd = {c: rng.choice(CHANNEL_DECISIONS) for c in g.channels}
    h = decode_via_heuristic(g, arch, cd, ba)
    e = decode_via_ilp(g, arch, cd, ba, time_budget_s=5.0)
    assert h.feasible and e.feasible
    assert validate_schedule(g, arch, e.schedule) == []
    if e.proven_optimal:
        assert e.period <= h.period


def test_capacity_enlargement_accommodates_schedule():
    """Decoded capacities must cover all in-flight tokens (Alg. 4 line 7)."""
    g, arch = fig1_graph(), one_tile_arch()
    ba = {"a1": P3, "a2": P3, "a5": P3, "a3": P1, "a4": P2}
    cd = {c: "PROD" for c in g.channels}
    res = decode_via_heuristic(g, arch, cd, ba)
    assert res.feasible
    for c, gamma in res.schedule.capacities.items():
        assert gamma >= g.channels[c].capacity or gamma >= 1


# ------------------------------------------------------- stored decodes
DIGESTS = os.path.join(os.path.dirname(__file__), "data", "caps_hms_digests.json")
# case -> (graph, ξ strategy, §VI pipeline delays); the unpipelined case
# keeps zero-delay channels, so actors become ready one by one.
STORED_CASES = {
    "multicamera.Reference": (lambda: apps.multicamera(pipelined=True), "Reference", True),
    "multicamera.MRB_Explore": (lambda: apps.multicamera(pipelined=True), "MRB_Explore", True),
    "sobel.MRB_Explore": (apps.sobel, "MRB_Explore", True),
    "multicamera.MRB_Explore.unpipelined": (apps.multicamera, "MRB_Explore", False),
}


def _sha(obj) -> str:
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()).hexdigest()


@pytest.mark.parametrize("case", sorted(STORED_CASES))
def test_caps_hms_reproduces_stored_decodes(case):
    """Twelve seeded genotypes per case decode to the stored schedules
    (period, every task start, bindings, capacities, periods_tried) and
    objective vectors, digested as SHA-256 of their sorted-key JSON."""
    with open(DIGESTS) as f:
        stored = json.load(f)[case]
    make_graph, strategy, pipelined = STORED_CASES[case]
    space = GenotypeSpace(make_graph(), paper_architecture())
    rng = random.Random(17)
    for i, want in enumerate(stored):
        genotype = space.random(rng, xi_mode(strategy))
        results = []

        def decoder(g, arch, decisions, binding, time_budget_s=None):
            results.append(decode_via_heuristic(g, arch, decisions, binding))
            return results[-1]

        ind = evaluate_genotype(space, genotype, decoder=decoder, pipelined=pipelined)
        assert _sha(results[0].to_json()) == want["decode"], (case, i)
        assert _sha(list(ind.objectives)) == want["objectives"], (case, i)
    assert len(stored) == 12


# ----------------------------------------------------- utilization sets
def _full_pass_add(starts, ends, pieces):
    """Reference add: insert every piece, then coalesce in one full pass."""
    for b, e in pieces:
        if b >= e:
            continue
        i = bisect.bisect_left(starts, b)
        starts.insert(i, b)
        ends.insert(i, e)
    i = 0
    while i + 1 < len(starts):
        if ends[i] >= starts[i + 1]:
            ends[i] = max(ends[i], ends[i + 1])
            del starts[i + 1]
            del ends[i + 1]
        else:
            i += 1


@settings(max_examples=60, deadline=None)
@given(seed=st.integers(0, 1_000_000), period=st.integers(1, 40))
def test_utilization_set_matches_full_pass_merge(seed, period):
    """Random add/remove/query sequences over wrapped, touching and
    overlapping pieces (validate_schedule adds overlaps on purpose): the
    neighbour-only merge leaves the same intervals as a full pass, and
    busy_end agrees with conflict(f_wrap(...))."""
    rng = random.Random(seed)
    u, ref = UtilizationSet(), UtilizationSet()
    for _ in range(80):
        op = rng.random()
        if op < 0.45:
            pieces = f_wrap(period, rng.randint(-period, 3 * period), rng.randint(0, period + 2))
            if rng.random() < 0.3:  # an arbitrary sequence, possibly empty
                pieces = [
                    (b, b + rng.randint(-1, 6)) for b in rng.sample(range(period), min(period, 3))
                ]
            u.add(pieces)
            _full_pass_add(ref.starts, ref.ends, pieces)
            assert (u.starts, u.ends) == (ref.starts, ref.ends)
        elif op < 0.6 and u.starts:
            i = rng.randrange(len(u.starts))
            b = rng.randint(u.starts[i], u.ends[i] - 1)
            e = rng.randint(b + 1, u.ends[i])
            u.remove([(b, e)])
            ref.remove([(b, e)])
            assert (u.starts, u.ends) == (ref.starts, ref.ends)
        else:
            start, dur = rng.randint(-2 * period, 4 * period), rng.randint(-1, period + 2)
            hit = u.conflict(f_wrap(period, start, dur))
            assert u.busy_end(period, start, dur) == (-1 if hit is None else hit[1])
