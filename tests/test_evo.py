"""Device-resident evolutionary loop (`repro.evo`): bit-for-bit ranking
parity against `repro.core.pareto` (including inf and duplicate points),
exact-evaluation front parity against the host ``nsga2`` explorer across
two scenario families and both decoders, the relaxed decode's relHV
tolerance gate, encoding round-trips, and the campaign/CLI wiring."""
import json
import math
import random

import pytest

from repro.core import (
    ExplorationProblem,
    crowding_distance,
    fast_nondominated_sort,
    get_explorer,
    relative_hypervolume,
)
from repro.scenarios import sample_scenarios

from conftest import tiny_campaign

jax = pytest.importorskip("jax")

from repro.evo import JaxNSGA2Explorer, PopulationLayout  # noqa: E402
from repro.evo.ranking import parity_rank_crowd  # noqa: E402


# -------------------------------------------------- ranking parity (fuzz)
def _host_rank_crowd(objs):
    """The host explorer's rank_crowd, reproduced from repro.core.pareto."""
    fronts = fast_nondominated_sort(objs)
    rank, crowd = {}, {}
    for fi, front in enumerate(fronts):
        d = crowding_distance(objs, front)
        for i in front:
            rank[i] = fi
            crowd[i] = d[i]
    return rank, crowd


def _random_objs(rng, n, k):
    """Random k-objective set with heavy duplication and inf coordinates —
    the regime where naive normalization / tie-breaking diverges."""
    vals = [0.0, 1.0, 2.0, 3.0, 4.0, math.inf]
    return [tuple(rng.choice(vals) for _ in range(k)) for _ in range(n)]


def test_ranking_parity_matches_host_pareto_with_inf_and_duplicates():
    rng = random.Random(42)
    for trial in range(25):
        n = rng.randint(1, 24)
        k = rng.randint(2, 4)
        objs = _random_objs(rng, n, k)
        h_rank, h_crowd = _host_rank_crowd(objs)
        d_rank, d_crowd = parity_rank_crowd(objs)
        assert d_rank == h_rank, f"trial {trial}: ranks diverge on {objs}"
        assert set(d_crowd) == set(h_crowd)
        for i in h_crowd:
            a, b = h_crowd[i], d_crowd[i]
            # bit-for-bit: inf matches inf, finite matches exactly
            assert a == b or (math.isinf(a) and math.isinf(b)), (
                f"trial {trial} point {i}: crowd {a!r} != {b!r} on {objs}"
            )


def test_ranking_parity_finite_fronts_bit_exact():
    rng = random.Random(7)
    for _ in range(10):
        n = rng.randint(2, 30)
        k = rng.randint(2, 5)
        objs = [
            tuple(float(rng.randint(0, 9)) for _ in range(k)) for _ in range(n)
        ]
        assert parity_rank_crowd(objs) == _host_rank_crowd(objs)


def test_ranking_parity_empty_and_singleton():
    assert parity_rank_crowd([]) == ({}, {})
    r, c = parity_rank_crowd([(1.0, 2.0)])
    assert r == {0: 0} and math.isinf(c[0])


# ------------------------------------------------------- exact front parity
CFG = dict(population=12, offspring=6, generations=4, seed=7)


def _parity_case(problem, **extra):
    cfg = dict(CFG, **extra)
    host = get_explorer("nsga2", **cfg).explore(problem)
    dev = get_explorer("jax_nsga2", evaluation="exact", **cfg).explore(problem)
    assert dev.front == host.front
    assert dev.history == host.history
    assert dev.evaluations == host.evaluations
    assert dev.meta.get("evaluation") == "exact"


@pytest.mark.parametrize("strategy", ["Reference", "MRB_Explore"])
def test_exact_parity_sobel_caps(strategy, sobel_arch):
    g, arch = sobel_arch
    _parity_case(
        ExplorationProblem(graph=g, arch=arch, strategy=strategy)
    )


def test_exact_parity_generated_scenario(gen_problem4):
    # second scenario family (stencil_chain), 4 objectives
    _parity_case(gen_problem4)


@pytest.mark.slow
def test_exact_parity_sobel_ilp(sobel_arch):
    g, arch = sobel_arch
    _parity_case(
        ExplorationProblem(
            graph=g, arch=arch, strategy="MRB_Explore", decoder="ilp",
            ilp_budget_s=2.0,
        ),
        population=8, offspring=4, generations=2,
    )


@pytest.mark.slow
def test_exact_parity_generated_scenario_ilp():
    sc = sample_scenarios(seed=3, n=1, families=["stencil_chain"])[0]
    _parity_case(
        ExplorationProblem.from_scenario(
            sc, decoder="ilp", ilp_budget_s=2.0,
            objectives=("period", "memory", "core_cost"),
        ),
        population=8, offspring=4, generations=2,
    )


# ---------------------------------------------------- relaxed decode gate
# Several seeds at 8 generations: the gate holds per seed, not for one
# lucky PRNG stream (JAX's default stream changed under this test once).
@pytest.mark.parametrize("seed", range(8, 16))
def test_relaxed_front_within_relhv_tolerance(sobel_arch, seed):
    g, arch = sobel_arch
    problem = ExplorationProblem(graph=g, arch=arch, strategy="Reference")
    cfg = dict(population=32, offspring=16, generations=8, seed=seed)
    host = get_explorer("nsga2", **cfg).explore(problem)
    dev = get_explorer("jax_nsga2", evaluation="relaxed", **cfg).explore(problem)
    assert dev.front, "relaxed exploration produced an empty front"
    # The archive is re-evaluated through the host engine, so the front is
    # made of true objective vectors; relHV against the host front gates
    # the relaxation quality (1.0 = covers the host front's hypervolume).
    relhv = relative_hypervolume(dev.front, host.front)
    assert relhv >= 0.25, f"relaxed relHV {relhv:.3f} below tolerance"
    assert dev.meta.get("evaluation") == "relaxed"
    assert dev.meta.get("relaxed_evaluations", 0) > 0


# --------------------------------------------------------------- encoding
def test_encoding_roundtrip_sobel(sobel_space):
    layout = PopulationLayout(sobel_space, xi_mode="explore")
    rng = random.Random(5)
    gts = [sobel_space.random(rng, "explore") for _ in range(16)]
    genes = layout.encode(gts)
    assert genes.shape == (16, layout.n_genes)
    back = layout.decode(genes)
    for orig, rt in zip(gts, back):
        assert rt.xi == orig.xi and rt.cd == orig.cd
        # β_A is stored normalized (idx % len(allowed)); decoding picks the
        # same core evaluate_genotype would.
        for a, bo, br in zip(sobel_space.actors, orig.ba, rt.ba):
            k = len(sobel_space.allowed[a])
            assert br == bo % k


def test_encoding_forced_xi_single_pattern(sobel_space):
    layout = PopulationLayout(sobel_space, xi_mode="always")
    rng = random.Random(5)
    genes = layout.encode([sobel_space.random(rng, "always") for _ in range(6)])
    pats = layout.xi_patterns(genes)
    assert len(pats) == 1
    assert all(v == 1 for v in pats[0][0])


# ------------------------------------------------------- campaign/CLI axis
def test_campaign_explorer_axis_expands_and_orders():
    camp = tiny_campaign(
        axes={
            "strategy": ["Reference"],
            "explorer": ["nsga2", "jax_nsga2"],
        }
    )
    cells = camp.expand()
    assert [c.explorer for c in cells] == ["nsga2", "jax_nsga2"]
    assert [c.coords.get("explorer") for c in cells] == ["nsga2", "jax_nsga2"]
    # a campaign without the axis keeps its cell list unchanged
    legacy = tiny_campaign()
    assert [c.explorer for c in legacy.expand()] == ["nsga2", "nsga2"]


def test_cli_explore_strategy_and_jax_explorer(tmp_path, capsys):
    from repro.cli import main

    sc = sample_scenarios(seed=0, n=1, families=["stencil_chain"])[0]
    spec = tmp_path / "prob.json"
    spec.write_text(json.dumps({"scenario": sc.to_json()}))
    rc = main(
        [
            "problem", "explore", str(spec),
            "--explorer", "jax_nsga2",
            "--strategy", "Reference",
            "--params", json.dumps(
                dict(population=6, offspring=4, generations=2, seed=0)
            ),
            "--out", str(tmp_path / "runs"),
        ]
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert "front=" in out and "saved ->" in out
    run_files = list((tmp_path / "runs").rglob("*.json"))
    assert run_files
    saved = json.loads(run_files[0].read_text())
    assert saved["explorer"] == "jax_nsga2"
    assert saved["problem"]["strategy"] == "Reference"


def test_explorer_registry_lists_jax_nsga2():
    from repro.core import explorer_names

    assert "jax_nsga2" in explorer_names()
    exp = get_explorer("jax_nsga2", population=4)
    assert isinstance(exp, JaxNSGA2Explorer)
    with pytest.raises(ValueError):
        get_explorer("jax_nsga2", evaluation="approximate")


# ------------------------------------------------------------ observability
def _within(inner, outer):
    return outer["ts"] <= inner["ts"] and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_generation_spans_and_retrace_counters(sobel_arch, monkeypatch, tmp_path):
    """Each device call is an ``evo.execute`` span holding its dispatch,
    wait and fetch; the finalization is one ``evo.finalize`` span holding
    the host re-decode and the hypervolume; JAX's compiles are counted."""
    from repro import obs

    d = str(tmp_path / "obs")
    monkeypatch.setenv(obs.OBS_ENV, "1")
    monkeypatch.setenv(obs.OBS_DIR_ENV, d)
    obs.configure(None)  # follow the (patched) environment
    try:
        g, arch = sobel_arch
        problem = ExplorationProblem(graph=g, arch=arch, strategy="Reference")
        get_explorer(
            "jax_nsga2", evaluation="relaxed",
            population=8, offspring=4, generations=2, seed=0,
        ).explore(problem)
        obs.flush()
        events = list(obs.iter_records(d))
    finally:
        obs.shutdown()
        obs.configure(None)
    spans = [e for e in events if e.get("t") == "span"]
    names = {e["name"] for e in spans}
    assert "explorer.generation" in names
    (tables,) = [s["attrs"] for s in spans if s["name"] == "evo.tables"]
    assert tables["actors"] <= tables["tasks"] <= tables["actors"] * tables["tmax"]
    assert {n for n in names if n.startswith("evo.")} == {
        "evo.tables", "evo.execute", "evo.dispatch", "evo.wait", "evo.fetch",
        "evo.finalize", "evo.final_decode", "evo.hypervolume"}

    executes = [s for s in spans if s["name"] == "evo.execute"]
    assert executes
    for ex in executes:
        kids = sorted((s for s in spans if s["name"] in ("evo.dispatch", "evo.wait", "evo.fetch")
                       and s["tid"] == ex["tid"] and _within(s, ex)), key=lambda s: s["ts"])
        assert [k["name"] for k in kids] == ["evo.dispatch", "evo.wait", "evo.fetch"]
    gens = [s for s in spans if s["name"] == "explorer.generation"]
    assert len(gens) == 2
    assert all(any(_within(ex, gen) for ex in executes) for gen in gens)

    (fin,) = [s for s in spans if s["name"] == "evo.finalize"]
    assert fin["ts"] >= max(gen["ts"] + gen["dur"] for gen in gens)
    for name in ("evo.final_decode", "evo.hypervolume"):
        (kid,) = [s for s in spans if s["name"] == name]
        assert _within(kid, fin)
    assert any(s["name"] == "engine.decode" and _within(s, fin) for s in spans)

    compiles = [e for e in events if e.get("t") == "counter" and e["name"] == "jax.compiles"]
    assert sum(e["value"] for e in compiles) >= 1
    counters = {e["name"] for e in events if e.get("t") == "counter"}
    assert not any(n.startswith("evo.") for n in counters)


# ------------------------------------------------------ named device parts
def _parts_in_hlo(lowered):
    """The part names that appear in a lowered program's op metadata."""
    import re

    text = lowered.as_text(dialect="hlo", debug_info=True)
    words = set()
    for stack in re.findall(r'op_name="([^"]*)"', text):
        words.update(re.findall(r"[A-Za-z_]\w*", stack))
    return words & {"rank", "vary", "decode", "simulate"}


def _relaxed_setup(strategy, seed=7):
    from repro.core.apps import sobel
    from repro.core.architecture import paper_architecture

    problem = ExplorationProblem(
        graph=sobel(), arch=paper_architecture(), strategy=strategy,
        objectives=("sim_period", "memory", "core_cost"),
    )
    exp = get_explorer("jax_nsga2", evaluation="relaxed", population=8,
                       offspring=4, generations=2, seed=seed)
    calls = []
    orig = exp._run_eval_plain

    def tap(fn, args, label):
        out = orig(fn, args, label)
        calls.append((label, fn, args, out))
        return out

    exp._run_eval_plain = tap
    return exp, problem, calls


def test_device_steps_carry_their_part_names():
    """Every jitted step names its parts (``jax.named_scope``), so a
    profile can attribute each device op to rank, vary, decode or
    simulate."""
    import numpy as np

    from repro.evo import ranking

    exp, problem, calls = _relaxed_setup("Reference")
    exp.explore(problem)
    label, fused, args, _ = calls[0]
    with jax.enable_x64(True):
        assert _parts_in_hlo(fused.lower(*args)) == {"rank", "vary", "decode", "simulate"}

    exp, problem, calls = _relaxed_setup("MRB_Explore")
    exp.explore(problem)
    steps = {label: (fn, args) for label, fn, args, _ in calls}
    with jax.enable_x64(True):
        fn, args = steps["vary"]
        assert _parts_in_hlo(fn.lower(*args)) == {"rank", "vary"}
        fn, args = steps["rank"]
        assert _parts_in_hlo(fn.lower(*args)) == {"rank"}
        (evaluator,) = [fn for key, fn in exp._eval_cache.items() if key[0] == (0,)]
        genes = np.asarray(steps["vary"][1][1])
        assert _parts_in_hlo(evaluator.lower(genes)) == {"decode", "simulate"}

    ranking.parity_rank_crowd([(1.0, 2.0), (2.0, 1.0)])
    keys = np.zeros((2, 2), np.int32)
    assert _parts_in_hlo(ranking._DOMINATION_JIT.lower(keys)) == {"rank"}


# The fused step's outputs at population 8, 4 offspring, seed 7, on Sobel
# with (sim_period, memory, core_cost): the second generation's survivors.
STORED_FUSED_F = [
    [24378.0, 74605800.0, 4.5], [19004.0, 80782800.0, 7.0],
    [18557.0, 91194600.0, 6.5], [31662.0, 74605800.0, 3.5],
    [19903.0, 80782800.0, 4.5], [25697.0, 74605800.0, 4.0],
    [22624.0, 97371600.0, 5.0], [25584.0, 74605800.0, 5.5],
]
STORED_FUSED_GENES = [
    [0, 1, 4, 1, 3, 1, 1, 0, 22, 9, 17, 22, 1, 17, 14],
    [0, 1, 1, 1, 2, 3, 3, 1, 4, 9, 19, 20, 17, 16, 12],
    [0, 0, 4, 0, 1, 0, 1, 1, 22, 6, 13, 1, 17, 0, 22],
    [0, 1, 2, 0, 3, 1, 3, 0, 22, 9, 17, 22, 17, 17, 14],
    [0, 1, 2, 1, 3, 1, 1, 0, 22, 9, 17, 22, 11, 17, 1],
    [0, 1, 4, 1, 3, 1, 1, 0, 22, 9, 17, 22, 11, 17, 14],
    [0, 3, 4, 1, 0, 1, 3, 1, 23, 20, 3, 5, 10, 23, 4],
    [0, 4, 4, 4, 0, 3, 4, 0, 14, 13, 0, 2, 18, 14, 8],
]


def test_fused_step_outputs_equal_a_stored_run():
    """Naming the parts changes metadata only: the fused step's outputs
    equal those stored from the step before it was named."""
    exp, problem, calls = _relaxed_setup("Reference")
    exp.explore(problem)
    label, _, _, (genes, F) = calls[-1]
    assert label == "gen" and len(calls) == 2
    assert genes.tolist() == STORED_FUSED_GENES
    assert F.tolist() == STORED_FUSED_F
