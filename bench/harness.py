"""One run of one benchmark cell: set-up, measured window, finalization,
the comparison that decides ``correct``, and the result line.

The cell drives the user's entry point,

    ExplorationProblem(...) -> get_explorer(...).explore(problem, engine=...,
                                                          on_generation=...)

with the configuration (``configs/<name>.json``) and the traffic mix
(``mixes/<name>.json``) that ``BENCHMARK.json`` names.  The harness marks
every generation boundary:

* the window opens at the end of the first generation in which JAX
  prepared no program (no compile and no load from the persistent cache),
  counted by a listener on JAX's own monitoring events;
* it closes at the first generation boundary at or after ``seconds``; the
  harness then sets the explorer's own ``time_budget_s`` to a small
  positive value, so ``explore()`` stops and finishes as a user sees it.

While the window is open the harness records, beside the program, what
the timed path produced (``Taps``); after the window, ``checks`` compares a
sample of it, drawn from the seed, with the plain reference under
``ref/``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CLOSE_BUDGET_S = 1e-9   # the explorer stops when explore() has run longer
UNBOUNDED_GENERATIONS = 10 ** 9


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load(kind: str, name: str) -> Dict[str, Any]:
    with open(BENCH / kind / f"{name}.json") as f:
        return json.load(f)


# ------------------------------------------------------------ compile count
class CompileCounter:
    """Counts programs JAX prepares for execution: one
    ``backend_compile_duration`` event per program, whether XLA compiled
    it or the persistent cache supplied it."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        from jax import monitoring

        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, _secs: float, **_kw) -> None:
        if name == self.EVENT:
            self.count += 1

    def close(self) -> None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on)


# ------------------------------------------------------------------ window
class Window:
    """Generation marks, the window's open and close, and the profiler."""

    def __init__(self, seconds: float, explorer, compiles: CompileCounter,
                 trace_dir: Optional[str]) -> None:
        self.seconds = seconds
        self.explorer = explorer
        self.compiles = compiles
        self.trace_dir = trace_dir
        self.state = "setup"
        self.marks: List[int] = []          # perf_counter_ns per generation
        self.t_open = self.t_close = None
        self.trace_t0 = None
        self.gens = 0                        # generations inside the window
        self.compiles_open = 0
        self.compiles_in_window = 0
        self._seen = 0

    def start(self) -> None:
        self._seen = self.compiles.count

    def on_generation(self, gen: int, run) -> None:
        now = time.perf_counter_ns()
        self.marks.append(now)
        count = self.compiles.count
        if self.state == "setup":
            if count == self._seen:
                self.state = "open"
                self.t_open = now
                self.compiles_open = count
                if self.trace_dir:
                    import jax

                    self.trace_t0 = time.perf_counter_ns()
                    jax.profiler.start_trace(self.trace_dir)
            self._seen = count
        elif self.state == "open":
            self.gens += 1
            if now - self.t_open >= self.seconds * 1e9:
                self.state = "closed"
                self.t_close = now
                self.compiles_in_window = count - self.compiles_open
                self.explorer.time_budget_s = CLOSE_BUDGET_S
                if self.trace_dir:
                    import jax

                    jax.profiler.stop_trace()

    @property
    def is_open(self) -> bool:
        return self.state == "open"


# -------------------------------------------------------------------- taps
class Taps:
    """Records the timed path's own inputs and outputs while the window is
    open.  The relaxed path's steps run through the explorer's
    ``_run_eval_plain`` (fused generation, ``vary``, ``rank``) and
    ``_run_eval`` (per-ξ-pattern ``decode``); the exact path ranks through
    ``repro.evo.explorer.parity_rank_crowd`` and decodes through the
    engine.  ``faults`` (tests only) alters an output where it is produced,
    before the program sees it."""

    def __init__(self, explorer, engine, window: Window,
                 faults: Optional[Dict[str, Callable]] = None) -> None:
        self.window = window
        self.faults = faults or {}
        self.steps: List[tuple] = []         # (label, args, out)
        self.ranks: List[tuple] = []         # (objs, rank, crowd)
        self.batches: List[list] = []        # window engine batches
        self._undo: List[Callable] = []
        self._wrap_method(explorer, "_run_eval_plain", self._plain)
        self._wrap_method(explorer, "_run_eval", self._eval)
        self._wrap_method(engine, "evaluate_batch", self._batch)
        import repro.evo.explorer as mod

        orig = mod.parity_rank_crowd
        mod.parity_rank_crowd = lambda objs: self._rank(orig, objs)
        self._undo.append(lambda: setattr(mod, "parity_rank_crowd", orig))

    def _wrap_method(self, obj, name: str, fn) -> None:
        orig = getattr(obj, name)
        setattr(obj, name, lambda *a, **k: fn(orig, *a, **k))
        self._undo.append(lambda: delattr(obj, name))

    def _rank(self, orig, objs):
        out = orig(objs)
        if "rank_crowd" in self.faults:
            out = self.faults["rank_crowd"](objs, out)
        if self.window.is_open:
            self.ranks.append((list(objs), out[0], out[1]))
        return out

    def _plain(self, orig, fn, args, label):
        out = orig(fn, args, label)
        if label in self.faults:
            out = self.faults[label](args, out)
        if self.window.is_open:
            self.steps.append((label, args, out))
        return out

    def _eval(self, orig, fn, genes, label):
        out = orig(fn, genes, label)
        if "decode" in self.faults:
            out = self.faults["decode"]((genes,), out)
        if self.window.is_open:
            self.steps.append(("decode", (genes,), out))
        elif self.window.state == "setup":
            self.steps.append(("setup_decode", (genes,), out))
        return out

    def _batch(self, orig, genotypes):
        out = orig(genotypes)
        if self.window.is_open:
            self.batches.append(out)
        return out

    def remove(self) -> None:
        for undo in reversed(self._undo):
            undo()


def warm_patterns(explorer, problem, engine, offspring: int, seed: int) -> None:
    """Prepare, before the run, every per-ξ-pattern evaluation the window
    can call: each pattern at every batch size from 1 to the offspring
    count, through the explorer's own per-pattern evaluator, so that
    whatever padding it applies, no program is first met in the window.
    With ξ explored, children split over patterns anew each generation.
    An explorer without a per-pattern evaluator needs no such warm-up: the
    window rule alone keeps its set-up compiles out of the window."""
    if not hasattr(explorer, "_eval_fn"):
        return
    import itertools

    import jax
    import numpy as np

    from repro.devices import ensure_compile_cache
    from repro.evo.encoding import PopulationLayout

    ensure_compile_cache()
    layout = PopulationLayout(engine.space, "explore")
    rng = np.random.default_rng(seed)
    with jax.enable_x64(True):
        for pattern in itertools.product((0, 1), repeat=layout.n_xi):
            fn = explorer._eval_fn(engine.space, pattern, problem.pipelined,
                                   tuple(problem.objectives))
            for n in range(1, offspring + 1):
                genes = (rng.random((n, layout.n_genes)) * layout.bounds).astype(np.int32)
                genes[:, layout.xi_slice] = pattern
                explorer._run_eval(fn, genes, "warm")


# ---------------------------------------------------------- layer metrics
def layer_metric(name: str) -> Callable:
    path = BENCH / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"layer_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: Dict[str, Any], cell: str, section: str) -> List[Dict[str, Any]]:
    """The metrics of ``section`` that this cell reports."""
    out = []
    for m in spec[section]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        if section == "per_layer" and "workloads" not in m:
            moved = next(e for e in spec["end_to_end"] if e["name"] == m["moves"])
            if "workloads" in moved and cell not in moved["workloads"]:
                continue
        out.append(m)
    return out


# --------------------------------------------------------------------- run
def run_cell(
    spec: Dict[str, Any],
    cell_name: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: int,
    require_tpu: bool = True,
    search: Optional[Dict[str, int]] = None,
    control: bool = False,
    faults: Optional[Dict[str, Callable]] = None,
) -> Dict[str, Any]:
    """Run one cell once; returns the result object.  ``search`` overrides
    the configuration's population and offspring (CPU rehearsal and tests
    only); ``control`` puts the reference at the next lower precision in
    the program's place for the comparison; ``faults`` (tests only) break
    the timed path underneath."""
    import jax

    cell = next(w for w in spec["workloads"] if w["name"] == cell_name)
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < cell["chips"]:
        raise NoChip(f"{len(devs)} device(s), the cell asks for {cell['chips']}")
    config = load("configs", cell["config"])
    mix = load("mixes", cell["traffic"])
    sizes = dict(population=config["population"], offspring=config["offspring"])
    sizes.update(search or {})

    from repro import obs
    from repro.core import ExplorationProblem, get_explorer
    from repro.core.architecture import ArchitectureGraph
    from repro.core.graph import ApplicationGraph

    work = tempfile.mkdtemp(prefix="bench-")
    try:
        obs_dir = os.path.join(work, "obs")
        trace_dir = os.path.join(work, "trace") if trace else None
        obs.configure(bool(trace), obs_dir)
        problem = ExplorationProblem(
            graph=ApplicationGraph.from_dict(config["graph"]),
            arch=ArchitectureGraph.from_dict(config["arch"]),
            objectives=tuple(mix["objectives"]),
            strategy=mix["strategy"],
            decoder=config["decoder"],
            pipelined=config["pipelined"],
        )
        explorer = get_explorer(
            mix["explorer"], evaluation=mix["evaluation"], seed=seed,
            generations=UNBOUNDED_GENERATIONS, **sizes,
        )
        engine = problem.make_engine(sim_backend=mix["sim_backend"])
        if mix.get("warm_patterns"):
            warm_patterns(explorer, problem, engine, sizes["offspring"], seed)
        compiles = CompileCounter()
        window = Window(seconds, explorer, compiles, trace_dir)
        taps = Taps(explorer, engine, window, faults)
        window.start()
        try:
            run = explorer.explore(problem, engine=engine,
                                   on_generation=window.on_generation)
        finally:
            taps.remove()
            compiles.close()
            engine.close()
        t_end = time.perf_counter_ns()
        if window.t_close is None:
            raise RuntimeError(f"the window never closed (state {window.state})")
        stats = devs[0].memory_stats() or {}
        device = {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": cell["chips"],
            "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
        }
        obs.flush()

        timing = {
            "setup_s": (window.t_open - t_start) / 1e9,
            "window_s": (window.t_close - window.t_open) / 1e9,
            "gens": window.gens,
            "evals_per_s": window.gens * sizes["offspring"]
            / ((window.t_close - window.t_open) / 1e9),
            "finalize_s": (t_end - window.t_close) / 1e9,
        }
        result: Dict[str, Any] = {}
        if trace:
            import tracing

            dev = tracing.reduce(trace_dir, window.trace_t0, window.t_close,
                                 devs[0].platform)
            spans = list(tracing.obs_spans(obs_dir))
            ctx = dict(spans=spans, window=window, device_trace=dev,
                       t_open=window.t_open, t_close=window.t_close)
            metrics = {}
            for m in cell_metrics(spec, cell_name, "per_layer"):
                value = layer_metric(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            device["busy_s"] = dev["busy_s"]
            device["window_s"] = dev["window_s"]
            result["breakdown"] = tracing.breakdown(dev, spans)
        else:
            metrics = {
                m["name"]: {"value": timing[m["name"]], "unit": m["unit"]}
                for m in cell_metrics(spec, cell_name, "end_to_end")
            }

        import checks

        t_check = time.perf_counter_ns()
        verdict = checks.compare(config, mix, taps, run, seed, sizes, control=control)
        timing["check_s"] = (time.perf_counter_ns() - t_check) / 1e9
        out = {
            "correct": verdict["correct"],
            "attempted": window.gens * sizes["offspring"],
            "failed": verdict["failed"],
            "metrics": metrics,
            "device": device,
        }
        out.update(result)
        out["timing"] = timing
        out["checks"] = verdict["numbers"]
        return out
    finally:
        shutil.rmtree(work, ignore_errors=True)
