"""Evaluation engine: content-addressed decode cache, parallel evaluator,
decoder parity on generated graphs, and the seed-front regression."""
import random

from repro.core import (
    DSEConfig,
    EvaluationEngine,
    GenotypeSpace,
    decode_key,
    evaluate_genotype,
    run_dse,
)
from repro.core.dse import Genotype
from repro.core.caps_hms import decode_via_heuristic
from repro.core.ilp import decode_via_ilp
from repro.scenarios import sample_scenario
from repro.scenarios.proptest import given, settings, st


# --------------------------------------------------------------- decode key
def test_decode_key_collapses_dead_alleles(sobel_space):
    """With ξ=1 the multi-cast actor's β_A gene and all member-channel C_d
    genes except the alphabetically-first member's are decoder-invisible."""
    sp = sobel_space
    mc = sp.mcast[0]
    members = sorted(sp.g.in_channels(mc) + sp.g.out_channels(mc))
    live, dead = members[0], members[1]
    i_live, i_dead = sp.channels.index(live), sp.channels.index(dead)
    i_mc = sp.actors.index(mc)

    base = Genotype((1,), (0,) * len(sp.channels), (0,) * len(sp.actors))

    def mutate_cd(gt, idx, v):
        cd = list(gt.cd)
        cd[idx] = v
        return Genotype(gt.xi, tuple(cd), gt.ba)

    def mutate_ba(gt, idx, v):
        ba = list(gt.ba)
        ba[idx] = v
        return Genotype(gt.xi, gt.cd, tuple(ba))

    assert decode_key(sp, base) == decode_key(sp, mutate_cd(base, i_dead, 3))
    assert decode_key(sp, base) == decode_key(sp, mutate_ba(base, i_mc, 5))
    assert decode_key(sp, base) != decode_key(sp, mutate_cd(base, i_live, 3))
    # with ξ=0 every allele is live
    kept = Genotype((0,), base.cd, base.ba)
    assert decode_key(sp, kept) != decode_key(sp, mutate_cd(kept, i_dead, 3))
    assert decode_key(sp, kept) != decode_key(sp, mutate_ba(kept, i_mc, 5))


def test_canonical_hit_shares_phenotype_keeps_identity(sobel_space):
    sp = sobel_space
    eng = EvaluationEngine(sp, cache_mode="canonical")
    mc = sp.mcast[0]
    dead = sorted(sp.g.in_channels(mc) + sp.g.out_channels(mc))[1]
    i_dead = sp.channels.index(dead)
    g1 = Genotype((1,), (0,) * len(sp.channels), (0,) * len(sp.actors))
    cd2 = list(g1.cd)
    cd2[i_dead] = 2
    g2 = Genotype(g1.xi, tuple(cd2), g1.ba)

    a = eng.evaluate(g1)
    b = eng.evaluate(g2)
    assert eng.stats()["evaluations"] == 1 and eng.hits == 1
    assert b.objectives == a.objectives
    assert b.genotype == g2  # identity preserved for crossover/mutation
    # and the shared phenotype equals a fresh decode of g2
    fresh = evaluate_genotype(sp, g2)
    assert fresh.objectives == b.objectives


def test_engine_matches_direct_evaluation(sobel_space):
    sp = sobel_space
    rng = random.Random(0)
    eng = EvaluationEngine(sp)
    for _ in range(10):
        gt = sp.random(rng)
        assert eng.evaluate(gt).objectives == evaluate_genotype(sp, gt).objectives


def test_cache_eviction_bounded(sobel_space):
    sp = sobel_space
    rng = random.Random(2)
    eng = EvaluationEngine(sp, max_entries=4)
    for _ in range(12):
        eng.evaluate(sp.random(rng))
    assert eng.stats()["entries"] <= 4


# ------------------------------------------------- run_dse regression suite
GOLDEN_CFG = dict(strategy="MRB_Explore", population=12, offspring=6, generations=4, seed=7)
# Front produced by the seed's run_dse (pre-engine, commit 0dad972) on this
# exact config — the memoized engine must reproduce it bit-for-bit.
GOLDEN_FRONT = [
    (15864.0, 58017000.0, 5.0),
    (17303.0, 58017000.0, 4.0),
    (23097.0, 60090600.0, 3.5),
]


def test_memoized_engine_reproduces_seed_front_bit_for_bit(sobel_arch):
    g, arch = sobel_arch
    res = run_dse(g, arch, DSEConfig(**GOLDEN_CFG, cache_mode="canonical"))
    assert res.front == GOLDEN_FRONT


def test_all_cache_modes_and_parallelism_agree(sobel_arch):
    g, arch = sobel_arch
    runs = {
        mode: run_dse(g, arch, DSEConfig(**GOLDEN_CFG, cache_mode=mode))
        for mode in ("none", "exact", "canonical")
    }
    par = run_dse(g, arch, DSEConfig(**GOLDEN_CFG, cache_mode="canonical", n_workers=2))
    fronts = {m: r.front for m, r in runs.items()}
    assert fronts["none"] == fronts["exact"] == fronts["canonical"] == par.front
    assert runs["none"].history == runs["exact"].history == runs["canonical"].history == par.history
    # canonical can only fold more decodes than exact, never fewer
    assert runs["canonical"].evaluations <= runs["exact"].evaluations <= runs["none"].evaluations
    assert runs["canonical"].cache_hits >= runs["exact"].cache_hits


def test_shared_engine_across_strategy_runs(sobel_arch):
    """One engine shared across strategy runs dedups forced-ξ fibers; the
    fronts stay identical to isolated runs."""
    g, arch = sobel_arch
    cfg = lambda s: DSEConfig(strategy=s, population=10, offspring=5, generations=3, seed=5)
    isolated = {s: run_dse(g, arch, cfg(s)).front for s in ("Reference", "MRB_Explore")}
    with EvaluationEngine(GenotypeSpace(g, arch)) as eng:
        shared_ref = run_dse(g, arch, cfg("Reference"), engine=eng)
        shared_exp = run_dse(g, arch, cfg("MRB_Explore"), engine=eng)
    assert shared_ref.front == isolated["Reference"]
    assert shared_exp.front == isolated["MRB_Explore"]
    # The second run starts warm: some of its decodes were already cached.
    assert shared_exp.cache_hits > 0


# ------------------------------------------------------ decoder differential
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_ilp_never_worse_than_heuristic_on_generated_graphs(seed):
    """Differential property on small generated scenarios: both decoders
    agree on feasibility and the exact decoder's period is ≤ CAPS-HMS's
    whenever its search completes (proven optimal)."""
    rng = random.Random(f"parity:{seed}")
    sc = sample_scenario(rng, family="random_dag")
    g, arch = sc.build()
    if len(g.actors) > 8:  # keep the exact search tractable
        g, arch = sample_scenario(random.Random(f"parity:{seed}:small"), "stencil_chain").build()
    cores = sorted(arch.cores)
    ba = {
        a: rng.choice([p for p in cores if g.actors[a].can_run_on(arch.cores[p].ctype)])
        for a in g.actors
    }
    from repro.core.binding import CHANNEL_DECISIONS

    cd = {c: rng.choice(CHANNEL_DECISIONS) for c in g.channels}
    h = decode_via_heuristic(g, arch, cd, ba)
    e = decode_via_ilp(g, arch, cd, ba, time_budget_s=3.0)
    assert h.feasible == e.feasible
    if e.feasible and e.proven_optimal:
        assert e.period <= h.period


# ------------------------------------------------------- sim_backend="auto"
def test_auto_backend_resolution_regimes():
    """One assertion per documented regime of resolve_sim_backend."""
    from repro.core.engine import AUTO_CPU_MAX_TASKS, AUTO_MIN_BATCH, resolve_sim_backend

    small, big = AUTO_CPU_MAX_TASKS, AUTO_CPU_MAX_TASKS + 1
    # tiny groups: per-phenotype events loop beats compiled dispatch
    assert resolve_sim_backend(AUTO_MIN_BATCH - 1, small, platform="cpu") == "events"
    assert resolve_sim_backend(AUTO_MIN_BATCH - 1, small, platform="tpu") == "events"
    # CPU: interpreter-mode pallas up to the structure bound, lax beyond
    assert resolve_sim_backend(AUTO_MIN_BATCH, small, platform="cpu") == "pallas"
    assert resolve_sim_backend(AUTO_MIN_BATCH, big, platform="cpu") == "vectorized"
    # TPU: the lax path owns batches (the Pallas round body does not
    # compile for TPU), whatever the structure size
    assert resolve_sim_backend(64, small, platform="tpu") == "vectorized"
    assert resolve_sim_backend(64, big, platform="tpu") == "vectorized"
    # GPU/unknown: portable lax path
    assert resolve_sim_backend(64, small, platform="gpu") == "vectorized"


def test_auto_backend_engine_end_to_end_and_metadata(sobel_arch):
    """sim_backend="auto" defers sim_period, resolves per ξ-group, records
    its choices, and stays value-identical to the events route."""
    from repro.core import ExplorationProblem, NSGA2Explorer

    g, arch = sobel_arch
    problem = ExplorationProblem(
        graph=g, arch=arch,
        objectives=("sim_period", "memory", "core_cost"),
        strategy="MRB_Always",
    )
    explorer = NSGA2Explorer(population=10, offspring=5, generations=1, seed=7)
    with problem.make_engine(sim_backend="auto") as eng:
        auto_run = explorer.explore(problem, engine=eng)
        assert eng.sim_backend_choices  # at least one group resolved
    with problem.make_engine(sim_backend="events") as eng:
        events_run = explorer.explore(problem, engine=eng)
    assert sorted(auto_run.front) == sorted(events_run.front)
    assert auto_run.meta["sim_backend"] == "auto"
    assert auto_run.meta["sim_backend_choices"]
    assert sum(auto_run.meta["sim_backend_choices"].values()) >= 1
    assert events_run.meta["sim_backend"] == "events"
    # metadata survives the ExplorationRun JSON round-trip
    import json as _json

    from repro.core import ExplorationRun

    rt = ExplorationRun.from_json(_json.loads(_json.dumps(auto_run.to_json())))
    assert rt.meta == auto_run.meta


def test_auto_backend_small_batch_routes_to_events(monkeypatch, sobel_arch):
    """Below AUTO_MIN_BATCH the auto engine must choose the event-driven
    loop (asserted via the recorded choice, single-genotype evaluate)."""
    from repro.core import ExplorationProblem

    g, arch = sobel_arch
    problem = ExplorationProblem(
        graph=g, arch=arch,
        objectives=("sim_period", "memory", "core_cost"),
        strategy="MRB_Always",
    )
    space = GenotypeSpace(problem.graph, problem.arch)
    rng = random.Random(0)
    with problem.make_engine(sim_backend="auto") as eng:
        for _ in range(6):  # singleton batches -> every group is size 1
            eng.evaluate(space.force_xi(space.random(rng), 1))
        assert set(eng.sim_backend_choices) == {"events"}


# ------------------------------------------------- sim circuit breaker (PR 9)
def test_sim_breaker_degrades_to_events_value_identical(sobel_arch, caplog):
    """A vectorized/pallas batch-sim failure opens the per-backend
    circuit for the engine's lifetime: later ξ-groups degrade to the
    event-driven reference backend, the failure is logged, the
    degradation is counted, and —
    because the backends are value-par — the front is identical to a
    clean events run."""
    from repro import faults
    from repro.core import ExplorationProblem, NSGA2Explorer
    from repro.faults import FaultPlan, FaultRule

    g, arch = sobel_arch
    problem = ExplorationProblem(
        graph=g, arch=arch,
        objectives=("sim_period", "memory", "core_cost"),
        strategy="MRB_Always",
    )
    explorer = NSGA2Explorer(population=10, offspring=5, generations=1, seed=7)
    faults.configure(FaultPlan(rules=[
        FaultRule("engine.sim_batch", "error", max_fires=1),
    ]))
    try:
        with caplog.at_level("ERROR", logger="repro.engine"), \
                problem.make_engine(sim_backend="vectorized") as eng:
            broken_run = explorer.explore(problem, engine=eng)
            assert "batched simulator 'vectorized' failed" in caplog.text
            assert "vectorized" in eng._sim_breaker_open
            assert eng.sim_degraded.get("vectorized", 0) >= 1
    finally:
        faults.reset()
    with problem.make_engine(sim_backend="events") as eng:
        events_run = explorer.explore(problem, engine=eng)
    assert sorted(broken_run.front) == sorted(events_run.front)
