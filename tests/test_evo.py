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
def test_generation_spans_and_retrace_counters(sobel_arch, monkeypatch, tmp_path):
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
    names = {e.get("name") for e in events}
    assert "explorer.generation" in names
    assert "evo.compile" in names  # first call of each jitted artifact
    assert "evo.execute" in names  # steady-state calls
    assert "evo.tables" in names
    assert any(e.get("name") == "evo.retraces" for e in events)
