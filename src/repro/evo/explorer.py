"""`jax_nsga2`: the device-resident NSGA-II explorer.

Registered alongside the host ``nsga2`` with the same problem/engine/run
seam and two evaluation paths selected by the ``evaluation`` parameter:

``evaluation="exact"`` (default)
    The host generation loop verbatim — same ``random.Random`` draw
    sequence, same engine decode — with the ranking core (non-dominated
    sort + crowding) replaced by the device ops of
    :mod:`repro.evo.ranking` through :func:`parity_rank_crowd`.  Fronts
    are **bit-identical** to the host explorer at any fixed seed; this is
    the safety net the parity tests pin.

``evaluation="relaxed"``
    The fully device-resident loop: the population lives as one int32
    gene matrix, objectives as one float64 matrix, and
    decode→simulate→rank→select→vary runs as jitted JAX — a *single*
    fused generation step whenever the strategy fixes ξ (the common
    paper configurations), or per-ξ-bucket evaluation jits plus shared
    ranking/variation jits when ξ is explored (the bucket set changes
    dynamically, so one static jit cannot cover it).  Candidate fitness
    uses the list-scheduling relaxation of :mod:`repro.evo.decode` (with
    the PR 4 simulator fused in when ``sim_period`` is an objective);
    the final archive is re-evaluated through the host engine so archived
    objective vectors mean exactly what every other explorer's do.  This
    path trades bit parity for throughput and is gated by a
    relative-hypervolume tolerance test instead.

Recompile avoidance: populations are padded to power-of-two batch sizes
and :class:`DecodeTables` are LRU-cached per ξ pattern, so steady-state
generations reuse compiled steps; JAX's own compile events, recorded by
:mod:`repro.obs` as the ``jax.compiles`` counter, make any residual
recompile visible in the trace export.

Telemetry: each device call is an ``evo.execute`` span split into
``evo.dispatch`` (the jitted call returns), ``evo.wait``
(``block_until_ready``) and ``evo.fetch`` (device→host copy); everything
after the last generation is ``evo.finalize``, with ``evo.final_decode``
(the host engine's re-evaluation) and ``evo.hypervolume`` inside.  Every
device step is cut into four named parts (``jax.named_scope``), so a
profile attributes each device op to one of them: ``rank`` (ranking,
crowding, truncation, the survivors' merge and gather), ``vary``
(tournament, crossover, mutation, forced genes), ``decode`` (the relaxed
decode) and ``simulate`` (the fused simulator).
"""
from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import replace
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import obs
from ..core.dse import Genotype, Individual, xi_mode
from ..core.explorers import (
    ExplorationRun,
    _check_engine,
    _finalize_hypervolume,
    _record_engine_meta,
    _update_archive,
    _xi_fixer,
    register_explorer,
)
from ..core.pareto import nondominated
from ..core.problem import ExplorationProblem
from ..devices import ensure_compile_cache
from .decode import RELAXED_OBJECTIVES, DecodeTables, make_relaxed_eval
from .encoding import PopulationLayout
from .ranking import (
    crowding,
    nondomination_ranks,
    parity_rank_crowd,
    truncation_order,
)
from .variation import init_population, mutate, tournament_pick, uniform_crossover

__all__ = ["JaxNSGA2Explorer"]


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


@register_explorer("jax_nsga2")
class JaxNSGA2Explorer:
    """NSGA-II with device-resident population and ranking (see module
    docstring for the exact/relaxed split)."""

    def __init__(
        self,
        *,
        population: int = 100,
        offspring: int = 25,
        generations: int = 2500,
        crossover_rate: float = 0.95,
        seed: int = 0,
        time_budget_s: Optional[float] = None,
        track_hypervolume: bool = True,
        evaluation: str = "exact",
        sim_iters: int = 32,
        max_patterns: int = 8,
    ) -> None:
        if evaluation not in ("exact", "relaxed"):
            raise ValueError("evaluation must be 'exact' or 'relaxed'")
        if population < 2 or offspring < 1:
            raise ValueError("population must be >= 2 and offspring >= 1")
        self.population = population
        self.offspring = offspring
        self.generations = generations
        self.crossover_rate = crossover_rate
        self.seed = seed
        self.time_budget_s = time_budget_s
        self.track_hypervolume = track_hypervolume
        self.evaluation = evaluation
        self.sim_iters = sim_iters
        self.max_patterns = max_patterns
        # Per-instance compiled-artifact caches (pattern → tables / jits).
        self._tables_cache: "OrderedDict[Tuple[int, ...], DecodeTables]" = OrderedDict()
        self._eval_cache: Dict[Any, Callable] = {}

    def params(self) -> Dict[str, Any]:
        return {
            "population": self.population,
            "offspring": self.offspring,
            "generations": self.generations,
            "crossover_rate": self.crossover_rate,
            "seed": self.seed,
            "time_budget_s": self.time_budget_s,
            "evaluation": self.evaluation,
        }

    # ------------------------------------------------------------------
    def explore(
        self,
        problem: ExplorationProblem,
        *,
        engine=None,
        on_generation: Optional[Callable[[int, ExplorationRun], None]] = None,
    ) -> ExplorationRun:
        t0 = time.monotonic()
        own_engine = engine is None
        if engine is None:
            engine = problem.make_engine()
        else:
            _check_engine(engine, problem)
        run = ExplorationRun(replace(problem), self.name, self.params())
        run.meta["evaluation"] = self.evaluation
        ev0, hit0, miss0 = engine.evaluations, engine.hits, engine.misses
        choices0 = dict(engine.sim_backend_choices)
        try:
            if self.evaluation == "exact":
                self._explore_exact(problem, engine, run, t0, on_generation)
                final = None
            else:
                final = self._explore_relaxed(problem, engine, run, t0, on_generation)
            with obs.span("evo.finalize", evaluation=self.evaluation):
                if final is not None:
                    self._final_decode(engine, run, *final)
                run.evaluations = engine.evaluations - ev0
                run.cache_hits = engine.hits - hit0
                run.cache_misses = engine.misses - miss0
                _record_engine_meta(run, engine, choices0)
                if self.track_hypervolume:
                    with obs.span("evo.hypervolume", generations=len(run.history)):
                        _finalize_hypervolume(run)
        finally:
            if own_engine:
                engine.close()
        run.wall_s = time.monotonic() - t0
        return run

    # ------------------------------------------------------- exact parity
    def _explore_exact(self, problem, engine, run, t0, on_generation) -> None:
        """The host NSGA-II loop with device ranking.  Every ``rng`` draw
        and its order matches :class:`repro.core.explorers.NSGA2Explorer`
        exactly — that is the bit-parity contract; only ``rank_crowd`` is
        swapped for the device implementation (which is itself bit-exact,
        see :mod:`repro.evo.ranking`)."""
        import random

        rng = random.Random(self.seed)
        mode = xi_mode(problem.strategy)
        space = engine.space
        fix = _xi_fixer(space, mode)
        pop = engine.evaluate_batch(
            [fix(space.random(rng, mode)) for _ in range(self.population)]
        )

        def rank_crowd(population: List[Individual]):
            objs = [i.objectives for i in population]
            with obs.span("evo.execute", kind="rank_parity", n=len(objs)) as sp:
                out = parity_rank_crowd(objs)
                sp.set(fronts=1 + max(out[0].values()) if out[0] else 0)
            return out

        def tournament(rank, crowd) -> Individual:
            i, j = rng.randrange(len(pop)), rng.randrange(len(pop))
            if (rank[i], -crowd.get(i, 0.0)) <= (rank[j], -crowd.get(j, 0.0)):
                return pop[i]
            return pop[j]

        _update_archive(run, pop)
        run.history.append([i.objectives for i in run.archive])
        ev0 = engine.evaluations

        for gen in range(self.generations):
            if self.time_budget_s and time.monotonic() - t0 > self.time_budget_s:
                break
            with obs.span(
                "explorer.generation", explorer=self.name, gen=gen
            ) as sp:
                rank, crowd = rank_crowd(pop)
                children: List[Genotype] = []
                for _ in range(self.offspring):
                    p1, p2 = tournament(rank, crowd), tournament(rank, crowd)
                    child = (
                        space.crossover(rng, p1.genotype, p2.genotype)
                        if rng.random() < self.crossover_rate
                        else p1.genotype
                    )
                    children.append(fix(space.mutate(rng, child, xi_mode=mode)))
                offspring = engine.evaluate_batch(children)
                merged = pop + offspring
                rank2, crowd2 = rank_crowd(merged)
                order = sorted(
                    range(len(merged)),
                    key=lambda i: (rank2[i], -crowd2.get(i, 0.0)),
                )
                pop = [merged[i] for i in order[: self.population]]
                _update_archive(run, pop)
                run.history.append([i.objectives for i in run.archive])
                sp.set(front=len(run.archive), evaluations=engine.evaluations - ev0)
            if on_generation:
                run.wall_s = time.monotonic() - t0
                on_generation(gen, run)

    # --------------------------------------------------- relaxed (device)
    def _tables(self, space, pattern: Tuple[int, ...], pipelined: bool) -> DecodeTables:
        tab = self._tables_cache.get(pattern)
        if tab is None:
            with obs.span("evo.tables", pattern=str(pattern)) as sp:
                tab = DecodeTables(space, pattern, pipelined=pipelined)
                sp.set(
                    actors=tab.A, channels=tab.C, tmax=int(tab.static["Tmax"]),
                    tasks=int(tab.static["n_tasks"].sum()),
                )
            self._tables_cache[pattern] = tab
            while len(self._tables_cache) > self.max_patterns:
                self._tables_cache.popitem(last=False)
        else:
            self._tables_cache.move_to_end(pattern)
        return tab

    def _eval_fn(self, space, pattern, pipelined, objectives):
        """Jitted padded relaxed evaluator for one ξ pattern (LRU over
        patterns; one compiled artifact per (pattern, pad) bucket)."""
        import jax

        key = (pattern, tuple(objectives))
        fn = self._eval_cache.get(key)
        if fn is None:
            tab = self._tables(space, pattern, pipelined)
            fn = jax.jit(make_relaxed_eval(tab, objectives, sim_iters=self.sim_iters))
            self._eval_cache[key] = fn
        return fn

    def _run_eval(self, fn, genes: np.ndarray, label: str) -> np.ndarray:
        """Pad to the power-of-two bucket, execute, unpad."""
        n = len(genes)
        pad = _bucket(max(1, n))
        if pad > n:
            genes = np.concatenate([genes, np.repeat(genes[:1], pad - n, 0)])
        with obs.span("evo.execute", kind=label, n=n, pad=pad):
            out = _execute(fn, (genes,))
        return out[:n]

    def _explore_relaxed(self, problem, engine, run, t0, on_generation):
        import jax
        import jax.random as jrandom

        objectives = tuple(problem.objectives)
        bad = [o for o in objectives if o not in RELAXED_OBJECTIVES]
        if bad:
            raise ValueError(
                f"objectives {bad} are not device-decodable; use "
                "evaluation='exact' for this problem"
            )
        mode = xi_mode(problem.strategy)
        space = engine.space
        layout = PopulationLayout(space, mode)
        pipelined = problem.pipelined
        G = layout.n_genes
        forced_mask = np.zeros(G, bool)
        forced_vals = np.zeros(G, np.int32)
        if layout.xi_forced is not None and layout.n_xi:
            forced_mask[layout.xi_slice] = True
            forced_vals[layout.xi_slice] = layout.xi_forced
        mut_mask = np.ones(G, bool)
        if mode != "explore":
            mut_mask[layout.xi_slice] = False
        relaxed_evals = 0

        def evaluate(genes: np.ndarray) -> np.ndarray:
            """Relaxed objectives for a host gene matrix, ξ-bucketed."""
            nonlocal relaxed_evals
            F = np.zeros((len(genes), len(objectives)), np.float64)
            for pattern, rows in layout.xi_patterns(genes):
                fn = self._eval_fn(space, pattern, pipelined, objectives)
                F[rows] = self._run_eval(fn, genes[rows], "decode")
            relaxed_evals += len(genes)
            return F

        def fold_archive(ag, aF, genes, F):
            """Nondominated-so-far archive over relaxed objectives
            (first-seen per objective vector, like the host archive)."""
            allg = np.concatenate([ag, genes]) if len(ag) else genes
            allF = np.concatenate([aF, F]) if len(ag) else F
            pts = [tuple(v) for v in allF]
            nd = set(nondominated([p for p in pts if any(np.isfinite(p))]))
            seen = set()
            keep = []
            for i, p in enumerate(pts):
                if p in nd and p not in seen:
                    keep.append(i)
                    seen.add(p)
            return allg[keep], allF[keep]

        ensure_compile_cache()
        with jax.enable_x64(True):
            key = jrandom.PRNGKey(self.seed)
            key, k0 = jrandom.split(key)
            genes = np.asarray(
                init_population(
                    k0,
                    self.population,
                    layout.bounds,
                    forced_mask if forced_mask.any() else None,
                    forced_vals,
                )
            )
            F = evaluate(genes)
            arch_g, arch_F = fold_archive(
                np.zeros((0, G), np.int32), np.zeros((0, len(objectives))), genes, F
            )
            run.history.append([tuple(v) for v in arch_F])

            # ξ fixed (or no multicast actors) → one pattern forever → the
            # whole generation is ONE jit: rank→select→vary→decode→
            # simulate→rank→truncate, no host round-trip.  Explored ξ
            # changes the bucket set dynamically, so evaluation jits are
            # per-pattern and only ranking/variation stay shared.
            single = layout.n_xi == 0 or layout.xi_forced is not None
            fused = None
            if single:
                pattern = (
                    (layout.xi_forced,) * layout.n_xi if layout.n_xi else ()
                )
                fused = self._fused_step(
                    space, pattern, pipelined, objectives,
                    layout.bounds, mut_mask, forced_mask, forced_vals,
                )
            else:
                vary_step, trunc_step = self._variation_jits(
                    layout.bounds, mut_mask, forced_mask, forced_vals
                )

            for gen in range(self.generations):
                if self.time_budget_s and time.monotonic() - t0 > self.time_budget_s:
                    break
                with obs.span(
                    "explorer.generation", explorer=self.name, gen=gen
                ) as sp:
                    key, kv = jrandom.split(key)
                    if fused is not None:
                        genes, F = self._run_eval_plain(fused, (kv, genes, F), "gen")
                        relaxed_evals += self.offspring
                    else:
                        children = self._run_eval_plain(vary_step, (kv, genes, F), "vary")
                        cF = evaluate(children)
                        mg = np.concatenate([genes, children])
                        mF = np.concatenate([F, cF])
                        sel = self._run_eval_plain(trunc_step, (mF,), "rank")[: self.population]
                        genes, F = mg[sel], mF[sel]
                    arch_g, arch_F = fold_archive(arch_g, arch_F, genes, F)
                    run.history.append([tuple(v) for v in arch_F])
                    sp.set(front=len(arch_F), evaluations=relaxed_evals)
                if on_generation:
                    run.wall_s = time.monotonic() - t0
                    on_generation(gen, run)

        run.meta["relaxed_evaluations"] = relaxed_evals
        return layout, np.concatenate([arch_g, genes])

    @staticmethod
    def _final_decode(engine, run, layout: PopulationLayout, rows: np.ndarray) -> None:
        """True objectives for the archive and the survivors: their relaxed
        vectors located promising genotypes; the host engine scores them."""
        with obs.span("evo.final_decode") as sp:
            uniq: List[Genotype] = list(dict.fromkeys(layout.decode(rows)))
            _update_archive(run, engine.evaluate_batch(uniq))
            sp.set(n=len(uniq))
        run.meta["relaxed_final_candidates"] = len(uniq)

    def _fused_step(
        self, space, pattern, pipelined, objectives,
        bounds, mut_mask, forced_mask, forced_vals,
    ):
        """The headline artifact: one jitted function

            ``(key, genes (μ,G), F (μ,k)) → (genes' (μ,G), F' (μ,k))``

        doing rank → crowding → tournament → crossover → mutation →
        relaxed decode (+ fused simulation when ``sim_period`` is asked
        for) → merged rank → elitist truncation, entirely on device.
        Shapes are static (μ, λ fixed per explorer instance), so it
        compiles once and every later generation is a single dispatch."""
        import jax
        import jax.numpy as jnp

        cache_key = ("fused", pattern, tuple(objectives))
        if cache_key in self._eval_cache:
            return self._eval_cache[cache_key]
        tab = self._tables(space, pattern, pipelined)
        raw_eval = make_relaxed_eval(tab, objectives, sim_iters=self.sim_iters)
        vary = _vary_body(self.crossover_rate, self.offspring,
                          bounds, mut_mask, forced_mask, forced_vals)
        mu = self.population

        def step(key, genes, F):
            child = vary(key, genes, F)
            cF = raw_eval(child)
            with jax.named_scope("rank"):
                mg = jnp.concatenate([genes, child])
                mF = jnp.concatenate([F, cF])
                sel = _elitist_order(mF)[:mu]
                return mg[sel], mF[sel]

        fn = jax.jit(step)
        self._eval_cache[cache_key] = fn
        return fn

    def _variation_jits(self, bounds, mut_mask, forced_mask, forced_vals):
        """Jitted rank→tournament→crossover→mutate step and the elitist
        μ+λ truncation step (shared across ξ buckets — gene matrices have
        one shape regardless of pattern)."""
        import jax

        cache_key = ("vary", len(bounds))
        if cache_key in self._eval_cache:
            return self._eval_cache[cache_key]

        def trunc(F):
            with jax.named_scope("rank"):
                return _elitist_order(F)

        vary = _vary_body(self.crossover_rate, self.offspring,
                          bounds, mut_mask, forced_mask, forced_vals)
        out = (jax.jit(vary), jax.jit(trunc))
        self._eval_cache[cache_key] = out
        return out

    def _run_eval_plain(self, fn, args, label: str):
        """Execute a jitted step without padding (shapes are already static
        per explorer configuration)."""
        with obs.span("evo.execute", kind=label):
            return _execute(fn, args)


def _execute(fn, args):
    """Call a jitted step and bring its result to the host, each phase a
    span of its own: launching, waiting for the device, copying back."""
    import jax

    with obs.span("evo.dispatch"):
        out = fn(*args)
    with obs.span("evo.wait"):
        out = jax.block_until_ready(out)
    with obs.span("evo.fetch"):
        return jax.tree.map(np.asarray, out)


def _vary_body(rate, count, bounds, mut_mask, forced_mask, forced_vals):
    """The traced parent ranking and variation shared by the fused step and
    the separate vary jit: ``(key, genes (μ,G), F (μ,k)) → children (λ,G)``."""
    import jax
    import jax.numpy as jnp
    import jax.random as jrandom

    bounds_d = jnp.asarray(bounds, jnp.int32)
    mut_d = jnp.asarray(mut_mask)
    forced_m = jnp.asarray(forced_mask)
    forced_v = jnp.asarray(forced_vals, jnp.int32)

    def vary(key, genes, F):
        with jax.named_scope("rank"):
            ranks = nondomination_ranks(F)
            crowd = crowding(F, ranks)
        with jax.named_scope("vary"):
            k1, k2, k3, k4 = jrandom.split(key, 4)
            ia = tournament_pick(k1, ranks, crowd, count)
            ib = tournament_pick(k2, ranks, crowd, count)
            child = uniform_crossover(k3, genes[ia], genes[ib], rate)
            child = mutate(k4, child, bounds_d, mut_d)
            return jnp.where(forced_m[None, :], forced_v[None, :], child)

    return vary


def _elitist_order(F):
    """Rows of the merged population in elitist (rank, −crowding) order."""
    ranks = nondomination_ranks(F)
    return truncation_order(ranks, crowding(F, ranks))
