"""Self-timed simulator subsystem: period measurement, analytic parity,
backend equality, trace/Gantt round-trips, the sim_period objective, and
the infeasible-period regression (ISSUE 3).

The heavy scenario-family parity sweep (all five families × both decoders
× vectorized backend) is marked slow; the fast tier keeps one structure
per concern so JIT compilation stays bounded.
"""
import json
import math
import random

import numpy as np
import pytest

from conftest import make_pipelined_sobel, random_decode
from repro.core import (
    ApplicationGraph,
    ExplorationProblem,
    NSGA2Explorer,
    OBJECTIVES,
    RandomSearchExplorer,
    multicast_actors,
    pipeline_delays,
    substitute_mrbs,
)
from repro.core.apps import multicamera, sobel
from repro.core.architecture import paper_architecture
from repro.core.caps_hms import DecodeResult, decode_via_heuristic
from repro.core.ilp import ExactResult
from repro.core.schedule import (
    attach_binding,
    comm_times,
    period_lower_bound,
)
from repro.scenarios import ArchParams, generate_architecture, sample_scenario
from repro.scenarios.proptest import given, settings, st
from repro.sim import (
    SimConfig,
    SimTrace,
    ascii_gantt,
    batch_simulate,
    check_sim_invariants,
    contention_free,
    measure_period,
    set_simulation_enabled,
    simulate,
    simulate_period,
    svg_gantt,
    trace_count,
)
from repro.sim.model import lower_phenotype, predict_horizon
from repro.sim.vectorized import INT32_SAFE_HORIZON, _unpack_task_code, lower_structure

NO_TRACE = SimConfig(trace=False)


# ------------------------------------------------------------ helpers
# (_pipelined_sobel / _random_decode moved to conftest.py: imported above
# as plain functions so the @given property tests can reach them too.)
def _paper_app(app, xi):
    """A paper application on the paper platform, every multicast actor
    substituted by its MRB (ξ = 1) or kept (ξ = 0), with §VI delays."""
    g, arch = app(), paper_architecture()
    return pipeline_delays(substitute_mrbs(g, {a: xi for a in multicast_actors(g)})), arch


def _lower_bound(gt, arch, sched):
    attach_binding(gt, sched.channel_binding)
    rt, wt = comm_times(gt, arch, sched.actor_binding, sched.channel_binding)
    return period_lower_bound(gt, arch, sched.actor_binding, rt, wt)


# ---------------------------------------------------- period measurement
def test_measure_period_simple_and_multiplicity():
    # Plain rate: every actor fires every 10 units.
    ft = {"a": list(range(0, 400, 10)), "b": list(range(3, 403, 10))}
    assert measure_period(ft) == 10.0
    # Multiplicity 2: intervals alternate 9, 11 → period (9+11)/2.
    ts, t = [], 0
    for i in range(40):
        ts.append(t)
        t += 9 if i % 2 == 0 else 11
    assert measure_period({"a": ts}) == 10.0


def test_measure_period_disconnected_components_take_max():
    slow = list(range(0, 1000, 50))
    fast = list(range(0, 140, 7))
    assert measure_period({"s": slow, "f": fast}) == 50.0


def test_measure_period_excludes_drain_tail():
    # Steady 10s, then a drained tail of fast intervals: the guard must
    # keep the steady value (the tail is ~len/4 long).
    ts, t = [], 0
    for _ in range(30):
        ts.append(t)
        t += 10
    for _ in range(6):
        ts.append(t)
        t += 3
    assert measure_period({"a": ts}) == 10.0


def test_measure_period_unconverged_returns_none():
    rng = random.Random(0)
    ts, t = [], 0
    for _ in range(40):
        ts.append(t)
        t += rng.randint(5, 50)
    assert measure_period({"a": ts}) is None


# ------------------------------------------------------- analytic parity
def test_single_core_mapping_matches_analytic_period():
    """All actors on one core, PROD placements: the core serializes every
    window, so self-timed period == analytic period == P_lb."""
    gt, arch = make_pipelined_sobel()
    core = sorted(arch.cores)[0]
    ba = {a: core for a in gt.actors}
    cd = {c: "PROD" for c in gt.channels}
    res = decode_via_heuristic(gt, arch, cd, ba)
    assert res.feasible
    sim = simulate(gt, arch, res.schedule, NO_TRACE)
    assert sim.converged and not sim.deadlocked
    assert sim.period == res.schedule.period == _lower_bound(gt, arch, res.schedule)


def test_contention_free_chain_matches_analytic_period():
    """Two actors on separate cores, channel in the producer's core-local
    memory: no resource is shared between actors (contention_free is True)
    and the simulated period equals the analytic one exactly."""
    g = ApplicationGraph("chain2")
    g.add_actor("A", {"t1": 7})
    g.add_actor("B", {"t1": 4})
    g.add_channel("c", "A", "B", delay=1, capacity=2, token_bytes=64)
    arch = generate_architecture(
        ArchParams(tiles=1, cores_per_tile=2, type_mix="fast_only"), seed=0
    )
    ba = {"A": sorted(arch.cores)[0], "B": sorted(arch.cores)[1]}
    res = decode_via_heuristic(g, arch, {"c": "PROD"}, ba)
    assert res.feasible
    assert contention_free(g, arch, res.schedule)
    sim = simulate(g, arch, res.schedule, NO_TRACE)
    assert sim.converged
    assert sim.period == res.schedule.period == _lower_bound(g, arch, res.schedule)
    assert check_sim_invariants(g, arch, res.schedule) == []


def test_contended_mapping_never_beats_lower_bound():
    gt, arch = make_pipelined_sobel()
    rng = random.Random(7)
    for _ in range(4):
        res = random_decode(gt, arch, rng)
        sim = simulate(gt, arch, res.schedule, NO_TRACE)
        assert not sim.deadlocked
        assert sim.period >= _lower_bound(gt, arch, res.schedule) - 1e-9


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_sim_invariants_on_generated_scenarios(seed):
    """Event-driven self-timed execution of decoded generated scenarios:
    never deadlocks, converges to a periodic regime, never beats P_lb, and
    equals the analytic period whenever the mapping is contention-free."""
    rng = random.Random(f"sim-prop:{seed}")
    sc = sample_scenario(rng)
    g, arch = sc.build()
    gt = pipeline_delays(
        substitute_mrbs(g, {a: rng.randint(0, 1) for a in multicast_actors(g)})
    )
    res = random_decode(gt, arch, rng)
    assert check_sim_invariants(gt, arch, res.schedule) == [], sc.name


# ------------------------------------------------------- backend parity
def test_vectorized_matches_events_on_sobel_batch():
    gt, arch = make_pipelined_sobel()
    rng = random.Random(3)
    scheds = [random_decode(gt, arch, rng).schedule for _ in range(4)]
    ev = [simulate(gt, arch, s, NO_TRACE) for s in scheds]
    vec = batch_simulate(gt, arch, scheds, NO_TRACE)
    for e, v in zip(ev, vec):
        assert e.fire_times == v.fire_times
        assert e.period == v.period
        assert e.deadlocked == v.deadlocked


def test_vectorized_matches_events_with_mrb_ports():
    gt, arch = make_pipelined_sobel()
    rng = random.Random(4)
    sched = random_decode(gt, arch, rng).schedule
    cfg = SimConfig(trace=False, mrb_ports=1)
    e = simulate(gt, arch, sched, cfg)
    (v,) = batch_simulate(gt, arch, [sched], cfg)
    assert e.fire_times == v.fire_times and e.period == v.period
    # Serializing every channel access cannot make execution faster.
    free = simulate(gt, arch, sched, NO_TRACE)
    assert e.period >= free.period - 1e-9


def test_pallas_backend_matches_events_on_sobel_batch():
    """The Pallas actor-step kernel (interpreter mode on CPU) executes the
    identical round program: bit-identical firing sequences and periods."""
    gt, arch = make_pipelined_sobel()
    rng = random.Random(5)
    scheds = [random_decode(gt, arch, rng).schedule for _ in range(3)]
    ev = [simulate(gt, arch, s, NO_TRACE) for s in scheds]
    vp = batch_simulate(gt, arch, scheds, NO_TRACE, backend="pallas")
    for e, v in zip(ev, vp):
        assert e.fire_times == v.fire_times
        assert e.period == v.period
        assert e.deadlocked == v.deadlocked


@pytest.mark.parametrize("xi", [0, 1])
def test_vectorized_matches_events_on_multicamera(xi):
    """Multicamera holds a 53-task actor (ξ = 0) and two-reader MRBs
    (ξ = 1): the packed per-task codes, durations and route bitmasks the
    rounds select reproduce the events backend's firing sequences."""
    gt, arch = _paper_app(multicamera, xi)
    rng = random.Random(21 + xi)
    scheds = [random_decode(gt, arch, rng).schedule for _ in range(2)]
    cfg = SimConfig(trace=False, iterations=8, max_iterations=8)
    ev = [simulate(gt, arch, s, cfg) for s in scheds]
    vec = batch_simulate(gt, arch, scheds, cfg)
    for e, v in zip(ev, vec):
        assert e.fire_times == v.fire_times
        assert e.period == v.period
        assert e.deadlocked == v.deadlocked


@pytest.mark.parametrize("app,xi", [(multicamera, 0), (multicamera, 1), (sobel, 1)])
def test_task_code_unpacks_to_one_hot_fields(app, xi):
    """Every (actor, task) code the simulator selects, padding included,
    unpacks to exactly the one-hot fields of ``ts_tab``."""
    gt, arch = _paper_app(app, xi)
    prog = lower_phenotype(gt, arch, random_decode(gt, arch, random.Random(0)).schedule)
    static, _ = lower_structure(prog)
    A, C, R, Tmax = (static[k] for k in ("A", "C", "R", "Tmax"))
    code = static["task_code"].reshape(A * Tmax)
    is_read, is_write, c_oh, s_oh = _unpack_task_code(
        code, np.arange(C), np.arange(R), C
    )
    unpacked = np.concatenate([is_read[:, None], is_write[:, None], c_oh, s_oh], axis=1)
    assert np.array_equal(unpacked, static["ts_tab"].reshape(A * Tmax, 2 + C + R) > 0)


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_batched_backend_reuses_compiled_functions():
    """ISSUE 4 satellite: a second, distinct, structure-identical batch
    must reuse the compiled simulator — no retrace (module trace-counter
    hook) — including with donated operand buffers (donation is a no-op
    warning on CPU)."""
    gt, arch = make_pipelined_sobel()
    rng = random.Random(6)
    batch1 = [random_decode(gt, arch, rng).schedule for _ in range(2)]
    batch2 = [random_decode(gt, arch, rng).schedule for _ in range(2)]
    batch_simulate(gt, arch, batch1, NO_TRACE, donate=True)
    before = trace_count()
    out = batch_simulate(gt, arch, batch2, NO_TRACE, donate=True)
    assert trace_count() == before, "structure-identical batch retraced"
    ev = [simulate(gt, arch, s, NO_TRACE) for s in batch2]
    assert [r.period for r in out] == [e.period for e in ev]
    assert [r.fire_times for r in out] == [e.fire_times for e in ev]


def test_int32_overflow_predicted_routes_to_events_backend(monkeypatch):
    """ISSUE 4 satellite: a phenotype whose predicted horizon exceeds the
    int32-safe bound must be routed to the exact event-driven backend (and
    never enter the compiled int32 path), with an identical result."""
    g = ApplicationGraph("huge")
    g.add_actor("A", {"t1": 2**24})
    g.add_actor("B", {"t1": 2**24})
    g.add_channel("c", "A", "B", delay=1, capacity=2, token_bytes=64)
    arch = generate_architecture(
        ArchParams(tiles=1, cores_per_tile=2, type_mix="fast_only"), seed=0
    )
    cores = sorted(arch.cores)
    res = decode_via_heuristic(
        g, arch, {"c": "PROD"}, {"A": cores[0], "B": cores[1]}
    )
    assert res.feasible
    prog = lower_phenotype(g, arch, res.schedule)
    assert predict_horizon(prog, NO_TRACE) > INT32_SAFE_HORIZON

    from repro.sim import vectorized as V

    def _boom(*a, **k):
        raise AssertionError("compiled int32 path used despite overflow risk")

    monkeypatch.setattr(V, "_run_batch", _boom)
    (v,) = batch_simulate(g, arch, [res.schedule], NO_TRACE)
    e = simulate(g, arch, res.schedule, NO_TRACE)
    assert v.fire_times == e.fire_times
    assert v.period == e.period


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_parity_sweep_families_and_decoders(seed):
    """Slow sweep: across scenario families and both decoders, all three
    backends — event-driven, fused-rounds lax, Pallas kernel (interpreter
    mode on CPU) — report identical firing sequences and periods, and
    every sim/analytic invariant holds."""
    rng = random.Random(f"sim-parity:{seed}")
    sc = sample_scenario(rng)
    g, arch = sc.build()
    gt = pipeline_delays(
        substitute_mrbs(g, {a: rng.randint(0, 1) for a in multicast_actors(g)})
    )
    decoder = "caps_hms" if seed % 2 == 0 else "ilp"
    res = random_decode(gt, arch, rng, decoder=decoder)
    e = simulate(gt, arch, res.schedule, NO_TRACE)
    (v,) = batch_simulate(gt, arch, [res.schedule], NO_TRACE)
    assert e.fire_times == v.fire_times, (sc.name, decoder)
    assert e.period == v.period
    (vp,) = batch_simulate(gt, arch, [res.schedule], NO_TRACE, backend="pallas")
    assert e.fire_times == vp.fire_times, (sc.name, decoder, "pallas")
    assert e.period == vp.period
    assert check_sim_invariants(gt, arch, res.schedule, result=e) == [], sc.name


# ------------------------------------------------------- trace & gantt
def test_trace_segments_do_not_overlap_and_roundtrip(tmp_path):
    gt, arch = make_pipelined_sobel()
    rng = random.Random(11)
    res = random_decode(gt, arch, rng)
    sim = simulate(gt, arch, res.schedule)
    trace = sim.trace
    assert trace is not None and trace.segments
    by_res = {}
    for s in trace.segments:
        assert s.end > s.start
        by_res.setdefault(s.resource, []).append((s.start, s.end))
    for r, ivals in by_res.items():
        ivals.sort()
        for (s1, e1), (s2, _) in zip(ivals, ivals[1:]):
            assert e1 <= s2, f"overlap on {r}"
    path = trace.save(str(tmp_path / "trace.json"))
    back = SimTrace.load(path)
    assert back.to_json() == trace.to_json()
    art = ascii_gantt(trace, width=80)
    assert any(a[0] in art.lower() for a in gt.actors)
    svg = svg_gantt(trace)
    assert svg.startswith("<svg") and svg.endswith("</svg>") and "rect" in svg


# --------------------------------------------------- sim_period objective
def test_sim_period_objective_registered_and_falls_back():
    assert "sim_period" in OBJECTIVES
    gt, arch = make_pipelined_sobel()
    rng = random.Random(13)
    res = random_decode(gt, arch, rng)
    from repro.core.problem import EvalContext, get_objective

    obj = get_objective("sim_period")
    ctx = EvalContext(gt, arch, res.schedule)
    measured = obj(ctx)
    assert measured == simulate_period(gt, arch, res.schedule)
    prev = set_simulation_enabled(False)
    try:
        assert obj(ctx) == float(res.schedule.period)
    finally:
        set_simulation_enabled(prev)


def test_explorer_end_to_end_with_sim_period(sobel_arch):
    """sim_period is selectable in an ExplorationProblem and drives a full
    explorer run; every feasible archive point carries a measured period
    that respects the lower bound."""
    g, arch = sobel_arch
    problem = ExplorationProblem(
        graph=g, arch=arch, strategy="MRB_Explore",
        objectives=("sim_period", "memory", "core_cost"),
    )
    run = RandomSearchExplorer(samples=12, batch=6, seed=3).explore(problem)
    assert run.problem.objectives == ("sim_period", "memory", "core_cost")
    feas = [i for i in run.archive if i.feasible]
    assert feas
    for ind in feas:
        assert ind.objectives[0] > 0
        assert math.isfinite(ind.objectives[0])


def test_engine_honours_sim_config_on_events_route(sobel_arch):
    """A non-default sim_config defers sim_period past decode so the
    engine's config reaches the simulator even without the vectorized
    backend (the inline objective can only use defaults)."""
    from repro.core import GenotypeSpace
    from repro.core.engine import EvaluationEngine

    g, arch = sobel_arch
    space = GenotypeSpace(g, arch)
    rng = random.Random(9)
    gt = space.random(rng)
    objs = ("sim_period", "memory", "core_cost")
    cfg = SimConfig(trace=False, mrb_ports=1)
    with EvaluationEngine(space, objectives=objs, sim_config=cfg) as eng:
        ind = eng.evaluate(gt)
    assert ind.feasible
    graph = eng._transformed(gt.xi)
    assert ind.objectives[0] == simulate_period(graph, arch, ind.schedule, cfg)
    with EvaluationEngine(space, objectives=objs) as eng2:
        default = eng2.evaluate(gt)
    # Serializing channel accesses can only slow execution down.
    assert ind.objectives[0] >= default.objectives[0] - 1e-9


@pytest.mark.slow
def test_engine_batched_backends_are_bit_identical(sobel_arch):
    g, arch = sobel_arch
    objs = ("sim_period", "memory", "core_cost")
    explorer = NSGA2Explorer(population=10, offspring=5, generations=2, seed=5)
    fronts = {}
    for backend in (None, "vectorized", "pallas"):
        problem = ExplorationProblem(
            graph=g, arch=arch, strategy="MRB_Explore", objectives=objs
        )
        with problem.make_engine(sim_backend=backend) as eng:
            run = explorer.explore(problem, engine=eng)
        fronts[backend] = run.front
    assert fronts[None] == fronts["vectorized"] == fronts["pallas"]


# --------------------------------------- infeasible-period regression
def test_infeasible_decode_period_is_inf():
    """ISSUE 3 satellite: an infeasible decode's period must be math.inf so
    period comparisons never prefer it (the old -1 sentinel did)."""
    assert DecodeResult(None, False).period == math.inf
    assert ExactResult(None, False, False).period == math.inf
    gt, arch = make_pipelined_sobel()
    core = sorted(arch.cores)[0]
    ba = {a: core for a in gt.actors}
    cd = {c: "GLOBAL" for c in gt.channels}
    bad = decode_via_heuristic(gt, arch, cd, ba, max_period=1)
    assert not bad.feasible
    assert bad.period == math.inf
    good = decode_via_heuristic(gt, arch, cd, ba)
    assert good.feasible
    # The whole point: min() over periods picks the feasible phenotype.
    assert min([bad, good], key=lambda r: r.period) is good
