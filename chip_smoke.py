#!/usr/bin/env python3
"""Bring-up smoke test: the exploration path on one TPU.

Drives the system through the entry points a user calls
(``ExplorationProblem`` -> ``get_explorer(...).explore``,
``EvaluationEngine(sim_backend="auto")``, the MRB kernels), in this one
process — a chip belongs to one process, so no pool is started:

A. relaxed ``jax_nsga2`` at population 512 / 256 offspring on the
   paper's platform with objectives (sim_period, memory, core_cost):
   ``Reference`` on Multicamera (the single fused generation step) and
   ``MRB_Explore`` on Sobel (per-ξ-pattern evaluation jits: one compile
   per (pattern, pad), so a graph with few multicast actors — Multicamera's
   23 would bring ~512 patterns at this population).  The front
   must be non-empty and every front point must re-decode to a schedule
   ``verify_schedule`` passes; relHV against host ``nsga2`` at the same
   budget, ``jax.compiles`` (programs JAX prepared during the run) and the
   first generation (compile) are printed.
B. exact ``jax_nsga2`` at the paper's population 100 / 25 offspring on
   Multicamera: front and history bit-identical to host ``nsga2``.
C. 64 feasible Multicamera decodes (all ξ = 1): the events and batched
   simulators give identical periods and firing sequences, and an
   ``EvaluationEngine(sim_backend="auto")`` generation uses the backend
   ``resolve_sim_backend`` picks for the device, with no degradation.
D. ``mrb_append`` / ``mrb_decode_attention`` at B=4, C=4096, kv=8, G=12,
   d=128 in bf16, compiled (not interpreted), against ``kernels/ref.py``.

Every phase prints one line tagged with the device.  The run fails when a
phase fails, when an engine degraded a batched simulation, when a batched
simulation took the int32 fallback, or when a Pallas kernel was built in
interpret mode.  Details go to ``chiprun_out/chip_smoke.json``.

    python3 chip_smoke.py                      # needs a TPU
    JAX_PLATFORMS=cpu python3 chip_smoke.py --cpu-rehearsal --tiny

The rehearsal runs the same phases on the CPU (kernels in interpret mode)
and always exits 1 without a result line: it is not a chip run.
"""
from __future__ import annotations

import argparse
import json
import random
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro import obs  # noqa: E402  (a checkout without src/ fails here)
from repro.core import ExplorationProblem, get_explorer, relative_hypervolume  # noqa: E402
from repro.core.apps import multicamera, sobel  # noqa: E402
from repro.core.architecture import paper_architecture  # noqa: E402
from repro.core.dse import GenotypeSpace, evaluate_genotype, transformed_graph  # noqa: E402
from repro.devices import DEFAULT_CACHE_DIR  # noqa: E402

OUT_DIR = ROOT / "chiprun_out"
OBS_DIR = OUT_DIR / "chip_smoke_obs"
SIM_OBJECTIVES = ("sim_period", "memory", "core_cost")

FULL = dict(
    a_pop=512, a_off=256, a_gens=4,
    b_pop=100, b_off=25, b_gens=3,
    c_batch=64,
    d_shape=(4, 4096, 8, 12, 128),
)
TINY = dict(
    a_pop=16, a_off=8, a_gens=2,
    b_pop=8, b_off=4, b_gens=2,
    c_batch=8,
    d_shape=(1, 512, 2, 4, 128),
)


class Smoke:
    def __init__(self, device) -> None:
        self.tag = f'[{device["platform"]} "{device["kind"]}" x{device["count"]}]'
        self.results = {}
        self.engines = []

    def phase(self, name, fn):
        t0 = time.monotonic()
        try:
            line, ok, detail = fn(self)
        except Exception as e:  # noqa: BLE001 — report the phase, run the rest
            traceback.print_exc()
            line, ok, detail = f"raised {type(e).__name__}: {e}", False, {}
        wall = time.monotonic() - t0
        self.results[name] = dict(ok=ok, wall_s=wall, line=line, **detail)
        status = "PASS" if ok else "FAIL"
        print(f"{name} {self.tag} {line} | wall_s={wall!r} | {status}", flush=True)


def _timed_explore(explorer, problem, engine=None):
    """Run one exploration; returns (run, first-generation seconds,
    steady per-generation seconds)."""
    marks = [time.monotonic()]
    run = explorer.explore(
        problem, engine=engine,
        on_generation=lambda gen, r: marks.append(time.monotonic()),
    )
    steps = [b - a for a, b in zip(marks, marks[1:])]
    return run, steps[0], steps[1:]


def _verify_front(problem, run):
    """Re-decode every archived front point with the problem's decoder and
    run it through the independent verifier: (checked, violations)."""
    from repro.verify import verify_schedule

    space = GenotypeSpace(problem.graph, problem.arch)
    front = set(run.front)
    checked = violations = 0
    for ind in run.archive:
        if not ind.feasible or ind.objectives not in front:
            continue
        again = evaluate_genotype(
            space, ind.genotype, decoder=problem.decoder, pipelined=problem.pipelined
        )
        gt = transformed_graph(space, ind.genotype.xi, problem.pipelined)
        report = verify_schedule(gt, problem.arch, again.schedule)
        checked += 1
        violations += len(report.violations)
    return checked, violations


def _counter(name, **match):
    total = 0
    for rec in obs.iter_records(str(OBS_DIR)):
        if rec.get("t") == "counter" and rec.get("name") == name:
            attrs = rec.get("attrs") or {}
            if all(attrs.get(k) == v for k, v in match.items()):
                total += rec.get("value", 0)
    return total


# ------------------------------------------------------------------ phases
def phase_a(sizes, app, strategy):
    def run(smoke):
        obs.flush()
        compiles0 = _counter("jax.compiles")
        problem = ExplorationProblem(
            graph=app(), arch=paper_architecture(),
            objectives=SIM_OBJECTIVES, strategy=strategy,
        )
        cfg = dict(
            population=sizes["a_pop"], offspring=sizes["a_off"],
            generations=sizes["a_gens"], seed=0,
        )
        dev_engine = problem.make_engine(sim_backend="auto")
        host_engine = problem.make_engine(sim_backend="auto")
        smoke.engines += [dev_engine, host_engine]
        dev, first_s, steady = _timed_explore(
            get_explorer("jax_nsga2", evaluation="relaxed", **cfg), problem, dev_engine
        )
        host = get_explorer("nsga2", **cfg).explore(problem, engine=host_engine)
        checked, violations = _verify_front(problem, dev)
        obs.flush()
        compiles = _counter("jax.compiles") - compiles0
        relhv = relative_hypervolume(dev.front, host.front) if dev.front else 0.0
        ok = bool(dev.front) and checked == len(dev.front) and violations == 0
        detail = dict(
            app=app.__name__, strategy=strategy, front=len(dev.front),
            host_front=len(host.front), verified=checked, violations=violations,
            relhv=relhv, compiles=compiles, first_generation_compile_s=first_s,
            steady_generation_s=steady, device_explore_wall_s=dev.wall_s,
            host_explore_wall_s=host.wall_s,
            sim_backend_choices=dict(dev_engine.sim_backend_choices),
        )
        line = (
            f"relaxed {strategy} on {app.__name__}: front={len(dev.front)} "
            f"verified={checked} violations={violations} relHV={relhv!r} "
            f"jax.compiles={compiles} compile(first generation)_s={first_s!r} "
            f"steady_generation_s={steady!r} device_wall_s={dev.wall_s!r} "
            f"host_nsga2_wall_s={host.wall_s!r}"
        )
        return line, ok, detail

    return run


def phase_b(sizes):
    def run(smoke):
        problem = ExplorationProblem(
            graph=multicamera(), arch=paper_architecture(), strategy="MRB_Explore"
        )
        cfg = dict(
            population=sizes["b_pop"], offspring=sizes["b_off"],
            generations=sizes["b_gens"], seed=0,
        )
        host = get_explorer("nsga2", **cfg).explore(problem)
        dev, first_s, steady = _timed_explore(
            get_explorer("jax_nsga2", evaluation="exact", **cfg), problem
        )
        same_front = dev.front == host.front
        same_history = dev.history == host.history
        ok = same_front and same_history and dev.evaluations == host.evaluations
        line = (
            f"exact MRB_Explore on multicamera: front={len(dev.front)} "
            f"bit-identical front={same_front} history={same_history} "
            f"evaluations={dev.evaluations}/{host.evaluations} "
            f"first_generation_s={first_s!r} device_wall_s={dev.wall_s!r} "
            f"host_nsga2_wall_s={host.wall_s!r}"
        )
        return line, ok, dict(front=len(dev.front), same_front=same_front,
                              same_history=same_history)

    return run


def phase_c(sizes, on_tpu):
    def run(smoke):
        from repro.core.engine import _task_count, resolve_sim_backend
        from repro.sim import batch_simulate
        from repro.sim.events import simulate
        from repro.sim.model import SimConfig

        arch = paper_architecture()
        decode = ExplorationProblem(
            graph=multicamera(), arch=arch, strategy="MRB_Always"
        ).make_engine()
        space = decode.space
        rng = random.Random(0)
        inds = []
        while len(inds) < sizes["c_batch"]:
            ind = decode.evaluate(space.force_xi(space.random(rng, "always"), 1))
            if ind.feasible:
                inds.append(ind)
        xi = inds[0].genotype.xi
        gt = transformed_graph(space, xi, True)
        scheds = [i.schedule for i in inds]

        t0 = time.monotonic()
        ev = [simulate(gt, arch, s, SimConfig(trace=False)) for s in scheds]
        times = {"events": time.monotonic() - t0}
        # The Pallas kernel's round body does not compile for TPU (see
        # repro.kernels.sim_step), so on the chip the batched side is the
        # lax backend alone.
        backends = ["vectorized"] if on_tpu else ["vectorized", "pallas"]
        same = {}
        for be in backends:
            t0 = time.monotonic()
            out = batch_simulate(gt, arch, scheds, backend=be)
            times[be] = time.monotonic() - t0
            same[be] = [(r.period, r.fire_times) for r in out] == [
                (r.period, r.fire_times) for r in ev
            ]

        problem = ExplorationProblem(
            graph=multicamera(), arch=arch, objectives=SIM_OBJECTIVES,
            strategy="MRB_Always",
        )
        engine = problem.make_engine(sim_backend="auto")
        smoke.engines.append(engine)
        t0 = time.monotonic()
        got = engine.evaluate_batch([i.genotype for i in inds])
        times["engine_auto"] = time.monotonic() - t0
        want = resolve_sim_backend(len(inds), _task_count(gt))
        engine_same = [g.objectives[0] for g in got] == [r.period for r in ev]
        ok = (
            all(same.values()) and engine_same
            and set(engine.sim_backend_choices) == {want}
            and not engine.sim_degraded
        )
        line = (
            f"simulators on {len(inds)} multicamera decodes (xi=1): "
            f"identical to events {same} engine(auto)={dict(engine.sim_backend_choices)} "
            f"expected={want} engine periods identical={engine_same} "
            f"degraded={dict(engine.sim_degraded)} seconds={times!r}"
        )
        return line, ok, dict(identical=same, backend=want, seconds=times)

    return run


def phase_d(sizes):
    def run(smoke):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.kernels.decode_attention import mrb_decode_attention
        from repro.kernels.mrb_ring import mrb_append
        from repro.kernels.ref import decode_attention_ref, mrb_append_ref

        B, C, kv, G, d = sizes["d_shape"]
        dt = jnp.bfloat16
        keys = jax.random.split(jax.random.PRNGKey(0), 5)
        q = (jax.random.normal(keys[0], (B, kv * G, d)) * 0.3).astype(dt)
        bk = (jax.random.normal(keys[1], (B, C, kv, d)) * 0.3).astype(dt)
        bv = (jax.random.normal(keys[2], (B, C, kv, d)) * 0.3).astype(dt)
        tok = jax.random.normal(keys[3], (B, 1, kv, d)).astype(dt)
        append_ok = True
        for omega in (0, 255, C - 1):
            out = mrb_append(bk, jnp.int32(omega), tok)
            ref = mrb_append_ref(bk, jnp.int32(omega), tok)
            append_ok &= bool(np.array_equal(np.asarray(out), np.asarray(ref)))
        errs = []
        for t, window, cap in ((C - 1, 0, 0.0), (2 * C + 777, C // 4, 30.0)):
            out = mrb_decode_attention(q, bk, bv, jnp.int32(t), window=window, softcap=cap)
            ref = decode_attention_ref(q, bk, bv, jnp.int32(t), window=window, softcap=cap)
            errs.append(float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))))
        tol = 2e-2  # the bf16 tolerance of tests/test_kernels.py
        ok = append_ok and max(errs) <= tol
        line = (
            f"MRB kernels B={B} C={C} kv={kv} G={G} d={d} bf16: "
            f"mrb_append exact={append_ok} mrb_decode_attention max_abs_err={errs!r} "
            f"(tol {tol})"
        )
        return line, ok, dict(append_exact=append_ok, attention_max_err=errs)

    return run


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true", help="tiny sizes (rehearsal)")
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="run the phases without a TPU; never prints a result",
    )
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.cpu_rehearsal:
        print(f"no TPU found: JAX platform is {device['platform']!r}", file=sys.stderr)
        return 2

    shutil.rmtree(OBS_DIR, ignore_errors=True)
    obs.configure(True, str(OBS_DIR))
    cache_dir = Path(jax.config.jax_compilation_cache_dir or DEFAULT_CACHE_DIR)
    cache_before = len(list(cache_dir.glob("*"))) if cache_dir.is_dir() else 0
    sizes = TINY if args.tiny else FULL
    smoke = Smoke(device)
    smoke.phase("A1", phase_a(sizes, multicamera, "Reference"))
    smoke.phase("A2", phase_a(sizes, sobel, "MRB_Explore"))
    smoke.phase("B", phase_b(sizes))
    smoke.phase("C", phase_c(sizes, on_tpu))
    smoke.phase("D", phase_d(sizes))
    obs.flush()

    degraded = [dict(e.sim_degraded) for e in smoke.engines if e.sim_degraded]
    fallbacks = _counter("sim.int32_fallbacks")
    interpreted = _counter("sim.pallas_builds", interpret=True)
    cache_after = len(list(cache_dir.glob("*"))) if cache_dir.is_dir() else 0
    guards_ok = not degraded and fallbacks == 0 and (interpreted == 0 or not on_tpu)
    print(
        f"guards {smoke.tag} engine.sim_degraded={degraded} "
        f"sim.int32_fallbacks={fallbacks} interpreted_pallas_builds={interpreted} "
        f"compile_cache={cache_dir} entries {cache_before}->{cache_after} "
        f"| {'PASS' if guards_ok else 'FAIL'}",
        flush=True,
    )
    ok = guards_ok and all(r["ok"] for r in smoke.results.values())
    OUT_DIR.mkdir(exist_ok=True)
    (OUT_DIR / "chip_smoke.json").write_text(json.dumps(
        dict(device=device, sizes=sizes, phases=smoke.results,
             degraded=degraded, int32_fallbacks=fallbacks,
             interpreted_pallas_builds=interpreted,
             compile_cache=dict(dir=str(cache_dir), before=cache_before,
                                after=cache_after)),
        indent=1, default=str,
    ))
    if not on_tpu:
        print("CPU rehearsal: not a chip run, no result", file=sys.stderr)
        return 1
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
