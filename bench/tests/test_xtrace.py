"""The readers that put the program's spans and the device ops on the
profile's own clock (``xtrace.py`` and the ``step_device_ms.<part>``,
``trace_coverage`` and ``hypervolume_s.finalize`` readers), on a trace
these tests record on the CPU: the relaxed explorer's fused step on Sobel
at a small size, with the program's telemetry on and the profiler running
over generations 1 to 3, as the harness's window does.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_xtrace.py
"""
from __future__ import annotations

import sys
import time
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import tracing  # noqa: E402
import xtrace  # noqa: E402

PART_METRICS = tuple(f"step_device_ms.{p}" for p in xtrace.PARTS)
OPEN, CLOSE = 0, 4          # generations that open and close the window


def _explore(tmp, on_generation, obs_on: bool):
    import jax  # noqa: F401  (the profiler bridge needs JAX imported)

    from repro import obs
    from repro.core import ExplorationProblem, get_explorer
    from repro.core.apps import sobel
    from repro.core.architecture import paper_architecture

    with pytest.MonkeyPatch.context() as mp:
        mp.delenv(obs.OBS_ENV, raising=False)
        obs.configure(obs_on, str(tmp / "obs"))
        try:
            problem = ExplorationProblem(
                graph=sobel(), arch=paper_architecture(), strategy="Reference",
                objectives=("sim_period", "memory", "core_cost"))
            get_explorer("jax_nsga2", evaluation="relaxed", population=8, offspring=4,
                         generations=CLOSE + 1, seed=3).explore(problem, on_generation=on_generation)
            obs.flush()
        finally:
            obs.configure(None)
    return list(tracing.obs_spans(str(tmp / "obs")))


def _record(tmp, obs_on: bool = True):
    import jax

    window = types.SimpleNamespace(trace_dir=str(tmp / "trace"), trace_t0=None)
    marks = {}

    def on_generation(gen, run):
        now = time.perf_counter_ns()
        if gen == OPEN:
            marks["open"] = now
            window.trace_t0 = time.perf_counter_ns()
            jax.profiler.start_trace(window.trace_dir)
        elif gen == CLOSE:
            marks["close"] = now
            jax.profiler.stop_trace()

    spans = _explore(tmp, on_generation, obs_on)
    return dict(spans=spans, window=window, t_open=marks["open"], t_close=marks["close"])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return _record(tmp_path_factory.mktemp("traced"))


def _read(name, ctx):
    return harness.layer_metric(name)(ctx)


def test_every_span_in_the_window_is_an_annotation_with_device_ops(traced):
    ctx = dict(traced)
    prof = xtrace.profile(ctx)
    gens = prof.named("explorer.generation")
    assert sorted(g.step for g in gens) == list(range(OPEN + 1, CLOSE + 1))
    execs = prof.named("evo.execute")
    assert len(execs) == CLOSE - OPEN
    assert all(prof.overlaps_op(a.start, a.end) for a in execs)
    # Each evo.execute annotation lies inside its generation and holds its
    # three phases, on the same clock as the ops.
    for name in ("evo.dispatch", "evo.wait", "evo.fetch"):
        assert len(prof.named(name)) == len(execs)
    for a in execs:
        assert any(g.start <= a.start and a.end <= g.end for g in gens)
    assert _read("trace_coverage", ctx) == 1.0


def test_parts_share_the_busy_time_of_each_generation(traced):
    ctx = dict(traced)
    values = {m: _read(m, ctx) for m in PART_METRICS}
    assert all(v is not None and v > 0 for v in values.values()), values
    per = ctx["part_ns"]
    parts = sum(per[p] for p in xtrace.PARTS)
    assert parts <= per["busy"] * (1 + 1e-9)
    assert parts >= 0.9 * per["busy"], per
    assert xtrace.profile(ctx).part_source == "stored HLO"
    assert len(xtrace.covered_generations(xtrace.profile(ctx))) == CLOSE - OPEN


def test_a_truncated_profile_lowers_trace_coverage(traced):
    """The device events of the second half of the window dropped, as a
    trace whose device buffer filled would hold them."""
    full = xtrace.profile(dict(traced))
    gens = sorted(full.named("explorer.generation"), key=lambda g: g.start)
    cut = gens[len(gens) // 2].start
    truncated = xtrace.Profile(full.annotations, [op for op in full.ops if op.end <= cut],
                               full.part_source)
    ctx = dict(traced, profile=truncated)
    coverage = _read("trace_coverage", ctx)
    assert coverage is not None and coverage < 1
    assert len(xtrace.covered_generations(truncated)) < len(gens)
    assert _read("step_device_ms.rank", ctx) > 0


def test_a_generation_cut_inside_its_execute_is_not_covered(traced):
    """The device buffer runs out partway through a generation's
    ``evo.execute``: that generation holds some of its ops, yet is left
    out of the generations held whole, and so are all after it."""
    full = xtrace.profile(dict(traced))
    gens = sorted(full.named("explorer.generation"), key=lambda g: g.start)
    victim = gens[2]
    (ex,) = [a for a in full.named("evo.execute")
             if victim.start <= a.start and a.end <= victim.end]
    ends = sorted(op.end for op in full.ops if ex.start <= op.start and op.end <= ex.end)
    cut = ends[len(ends) // 2]
    truncated = xtrace.Profile(full.annotations, [op for op in full.ops if op.end <= cut],
                               full.part_source)
    assert truncated.overlaps_op(ex.start, ex.end)
    covered = xtrace.covered_generations(truncated)
    assert sorted(g.step for g in covered) == [g.step for g in gens[:2]]
    per = xtrace.part_ns_per_generation(truncated)
    whole = xtrace.part_ns_per_generation(full)
    assert per["busy"] > 0.5 * whole["busy"]


def test_json_spans_map_onto_their_annotations(traced):
    """The JSON-lines start of each generation, put on the profile's clock
    through the window's ``trace_t0``, lands near its annotation."""
    ctx = dict(traced)
    offsets = xtrace.annotation_offsets(xtrace.profile(ctx), ctx["spans"],
                                        ctx["window"].trace_t0)
    assert len(offsets) == CLOSE - OPEN
    assert max(abs(o) for o in offsets) < 50_000_000       # 50 ms


def test_readers_are_silent_on_a_program_without_annotations(tmp_path):
    """A program that records no spans into the profile (telemetry off, as
    the parent of this reader records none) gives no value, and no error."""
    ctx = _record(tmp_path, obs_on=False)
    for name in PART_METRICS + ("trace_coverage",):
        assert _read(name, ctx) is None, name
    assert not xtrace.profile(ctx).named("explorer.generation")


def test_tpu_op_events_are_named_from_their_hlo_text(traced):
    """A TPU op event carries its HLO text as its name and no op stats, and
    its program is the ``XLA Modules`` event running then.  The fused
    step's CPU op events, written so, get the parts their own stats give."""
    from types import SimpleNamespace as NS

    from jax.profiler import ProfileData

    path = xtrace.trace_file(traced["window"].trace_dir)
    cpu, tpu = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if stats.get("hlo_module") == "jit_step" and ev.duration_ns > 0:
                    cpu.append(ev)
                    tpu.append(NS(name=f"%{stats['hlo_op']} = f32[8]{{0}} fusion(f32[8]{{0}} %p.1)",
                                  start_ns=ev.start_ns, duration_ns=ev.duration_ns,
                                  stats=[("device_offset_ps", 0)]))
                    program = stats["program_id"]
    assert tpu
    lo = min(ev.start_ns for ev in tpu)
    hi = max(ev.start_ns + ev.duration_ns for ev in tpu)
    lines = {"XLA Ops": NS(events=sorted(tpu, key=lambda e: e.start_ns)),
             "XLA Modules": NS(events=[NS(name=f"jit_step({program})", start_ns=lo,
                                          duration_ns=hi - lo, stats=[])])}
    cpu_namer = xtrace._OpNamer(path)
    want = sorted((ev.start_ns, cpu_namer.op(ev).part) for ev in cpu)
    namer = xtrace._OpNamer(path)
    got = sorted((op.start, op.part) for op in xtrace.device_ops(lines, namer))
    assert got == want
    assert {p for _, p in got} >= set(xtrace.PARTS)
    assert set(namer.sources) == {"stored HLO"}


def test_hypervolume_reads_the_finalization_span():
    spans = [{"name": "evo.hypervolume", "ts": 50, "dur": 2_000_000_000},
             {"name": "evo.hypervolume", "ts": 5, "dur": 7}]
    ctx = dict(spans=spans, t_open=0, t_close=10)
    assert _read("hypervolume_s.finalize", ctx) == 2.0
    assert _read("hypervolume_s.finalize", dict(ctx, spans=spans[1:])) is None


def test_part_of_reads_the_innermost_part():
    assert xtrace.part_of("jit(step)/vmap(decode)/while/body/add") == "decode"
    assert xtrace.part_of("jit(step)/vary/vmap()/closed_call/simulate/mul") == "simulate"
    assert xtrace.part_of("jit(step)/nondomination_ranks/add") is None
    assert xtrace.part_of("") is None
