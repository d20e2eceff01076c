"""§Perf A/B experiments: lower one cell twice with a single change and
diff the roofline terms — the clean hypothesis → change → measure loop.

Run (one experiment, ~2-10 min each):
  PYTHONPATH=src python -m benchmarks.perf_ab --exp ce_mode
  PYTHONPATH=src python -m benchmarks.perf_ab --exp microbatch
  PYTHONPATH=src python -m benchmarks.perf_ab --exp decode_capacity
  PYTHONPATH=src python -m benchmarks.perf_ab --exp dse_cache
  PYTHONPATH=src python -m benchmarks.perf_ab --exp sim_backends
  PYTHONPATH=src python -m benchmarks.perf_ab --exp service
  PYTHONPATH=src python -m benchmarks.perf_ab --exp evo
"""
import os
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
    ).strip()

import argparse
import json

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config
from repro.launch.hlo import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models.model import init_model
from repro.optim import make_optimizer
from repro.runtime.shardings import (
    batch_specs_for_mesh, named, param_specs, state_specs,
)
from repro.runtime.train import TrainState, make_train_step
from repro.data import batch_specs

PEAK, HBM, ICI = 197e12, 819e9, 50e9


def bench_provenance():
    """Git SHA + hostname stamped into every BENCH_*.json write, so
    history entries from different machines/commits stay attributable
    (the ±20% regression gates compare against the last entry — knowing
    *where* that entry came from is what makes a gate trip actionable)."""
    import socket
    import subprocess

    sha = None
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        sha = out.stdout.strip() or None
    except Exception:
        pass
    return {"git_sha": sha, "host": socket.gethostname()}


def lower_train(arch: str, *, ce_mode="onehot", microbatches=None, seq=4096, batch=256):
    spec = get_config(arch)
    cfg = spec.model
    mesh = make_production_mesh()
    params_s = jax.eval_shape(lambda r: init_model(r, cfg), jax.random.PRNGKey(0))
    p_specs = param_specs(params_s, mesh, grouped_blocks=cfg.shared_attn_every > 0)
    opt_init, opt_update = make_optimizer(spec.optimizer, 1e-4)
    opt_s = jax.eval_shape(opt_init, params_s)
    o_specs = type(opt_s)(
        jax.sharding.PartitionSpec(),
        state_specs(opt_s.inner, mesh, grouped_blocks=cfg.shared_attn_every > 0),
    )
    st = TrainState(params_s, opt_s)
    st_specs = TrainState(p_specs, o_specs)
    b_s = batch_specs(cfg, seq, batch)
    b_specs = batch_specs_for_mesh(b_s, mesh)
    mb = microbatches if microbatches is not None else spec.train_microbatches
    step = make_train_step(
        cfg, opt_update, microbatches=mb, grad_dtype=spec.grad_dtype,
        grad_shardings=named(mesh, p_specs), ce_mode=ce_mode,
    )
    jitted = jax.jit(
        step, in_shardings=(named(mesh, st_specs), named(mesh, b_specs)),
        donate_argnums=(0,),
    )
    with jax.set_mesh(mesh):
        compiled = jitted.lower(st, b_s).compile()
    return report(compiled)


def report(compiled):
    cost = analyze_hlo(compiled.as_text())
    mem = compiled.memory_analysis()
    per_dev = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    return {
        "flops": cost.flops,
        "hbm_bytes": cost.bytes,
        "collective_bytes": cost.collective_bytes,
        "collectives": dict(cost.collectives),
        "compute_s": cost.flops / PEAK,
        "memory_s": cost.bytes / HBM,
        "collective_s": cost.collective_bytes / ICI,
        "mem_gib": per_dev / 2**30,
    }


def show(tag, r):
    print(
        f"{tag:28s} compute={r['compute_s']:.3f}s memory={r['memory_s']:.3f}s "
        f"collective={r['collective_s']:.3f}s mem={r['mem_gib']:.2f}GiB",
        flush=True,
    )
    return r


def dse_cache_ab(repeats: int = 5):
    """A/B the memoized evaluation engine on the Sobel benchmark config
    (SCALE['Sobel']: 30 generations, population 24, offspring 10, seed 11,
    all three strategies).  Each arm is a 3-cell :class:`repro.core.Campaign`
    (the strategy axis) executed by the shared CampaignRunner into an
    in-memory RunStore, so every repeat re-executes every cell and the
    sweep logic is the production campaign path, not a hand-rolled loop.
    Arms differ only in the campaign's engine kwargs:

      no_memo   no decode memoization, no ξ-transform cache
      seed      the pre-engine run_dse: exact-genotype memoization only
      engine    content-addressed canonical key + ξ-transform LRU

    Pareto fronts must be bit-identical across all arms — the engine
    changes wall time only.  Arms are interleaved and the per-arm minimum
    reported: shared-container wall-clock noise swamps sequential medians.
    BENCH_dse.json keeps a ``history`` list — every run appends the
    previous head — so the bench trajectory across PRs is inspectable,
    and the run *fails* (CI slow job) when an engine speedup drops below
    the last recorded value by more than 20% (set REPRO_BENCH_NO_GATE=1
    to bypass).
    """
    from repro.core import Campaign, CampaignRunner, RunStore, paper_architecture, sobel

    g, arch = sobel(), paper_architecture()
    arms = {
        "no_memo": dict(cache_mode="none", transform_cache=0),
        "seed": dict(cache_mode="exact", transform_cache=0),
        "engine": dict(cache_mode="canonical", transform_cache=64),
    }
    strategies = ("Reference", "MRB_Always", "MRB_Explore")

    def arm_campaign(arm):
        # track_hypervolume=False: the timed arms measure decode/cache
        # work, not hypervolume post-processing; share_engines=False keeps
        # every strategy cell cold-cache (the historical per-strategy
        # fresh-engine loop).
        return Campaign(
            name=f"dse-cache-{arm}",
            problems=[{"label": "Sobel", "graph": g.to_dict(), "arch": arch.to_dict()}],
            axes={"strategy": list(strategies)},
            explorer="nsga2",
            explorer_params={"population": 24, "offspring": 10, "generations": 30,
                             "seed": 11, "track_hypervolume": False},
            engine=arms[arm],
            share_engines=False,
        )

    campaigns = {arm: arm_campaign(arm) for arm in arms}
    tags = {arm: [c.tag for c in campaigns[arm].expand()] for arm in arms}

    def run_arm(arm):
        res = CampaignRunner(campaigns[arm], store=RunStore(None)).run()
        # Arm wall = Σ per-cell exploration wall (the explorers' own
        # clocks), so the runner's report/hypervolume post-processing
        # stays out of the timed window — matching track_hypervolume=False
        # and the pre-campaign baseline.
        wall = sum(res.cells[t]["wall_s"] for t in tags[arm])
        fronts = [res.front(t) for t in tags[arm]]
        decodes = sum(res.cells[t]["evaluations"] for t in tags[arm])
        hits = sum(res.cells[t]["cache_hits"] for t in tags[arm])
        return wall, fronts, decodes, hits

    run_arm("no_memo")  # warm-up
    walls = {a: [] for a in arms}
    last = {}
    for _ in range(repeats):
        for arm in arms:
            w, fronts, decodes, hits = run_arm(arm)
            walls[arm].append(w)
            last[arm] = (fronts, decodes, hits)
    results = {}
    for arm in arms:
        fronts, decodes, hits = last[arm]
        results[arm] = {"wall_s": min(walls[arm]), "decodes": decodes, "hits": hits}
        print(
            f"arm={arm:8s} wall={results[arm]['wall_s']:.2f}s "
            f"decodes={decodes} hits={hits}",
            flush=True,
        )
    fronts_identical = last["no_memo"][0] == last["seed"][0] == last["engine"][0]
    assert fronts_identical, "Pareto fronts diverged across engine arms"
    for arm in ("seed", "engine"):
        print(
            f"speedup {arm} vs no_memo: "
            f"{results['no_memo']['wall_s'] / results[arm]['wall_s']:.2f}x"
        )
    print(
        f"speedup engine vs seed: "
        f"{results['seed']['wall_s'] / results['engine']['wall_s']:.2f}x "
        f"({results['seed']['decodes'] - results['engine']['decodes']} decodes saved)"
    )
    print("fronts bit-identical across all arms: OK")

    speedups = {
        "engine_vs_no_memo": results["no_memo"]["wall_s"] / results["engine"]["wall_s"],
        "engine_vs_seed": results["seed"]["wall_s"] / results["engine"]["wall_s"],
    }
    bench_path = os.path.join(os.path.dirname(__file__), "..", "BENCH_dse.json")
    prev = None
    try:
        with open(bench_path) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        pass
    prev_speedups = None
    if prev:
        # Pre-history files carried only the flat speedup_* keys.
        prev_speedups = prev.get("speedups") or {
            k: prev.get(f"speedup_{k}") for k in speedups
        }
    history = list(prev.get("history", [])) if prev else []
    if prev:
        history.append(
            {
                "arms": prev.get("arms"),
                "speedups": prev_speedups,
                "fronts_identical": prev.get("fronts_identical"),
                "git_sha": prev.get("git_sha"),
                "host": prev.get("host"),
            }
        )
    bench = {
        **bench_provenance(),
        "experiment": "dse_cache",
        "config": {"population": 24, "offspring": 10, "generations": 30,
                   "seed": 11, "strategies": list(strategies),
                   "driver": "campaign"},
        "arms": results,
        "speedups": speedups,
        # Legacy keys kept for readers of the pre-history schema.
        "speedup_engine_vs_no_memo": speedups["engine_vs_no_memo"],
        "speedup_engine_vs_seed": speedups["engine_vs_seed"],
        "fronts_identical": fronts_identical,
        "history": history[-24:],
    }
    # Regression gate (CI slow job): each engine speedup must stay within
    # 20% of its last recorded value.  Checked before the write so a
    # regressed run never replaces the baseline it failed against.
    if prev and not os.environ.get("REPRO_BENCH_NO_GATE"):
        for name, s in speedups.items():
            last_s = prev_speedups.get(name)
            if last_s and s < 0.8 * last_s:
                raise SystemExit(
                    f"dse_cache regression: {name} speedup {s:.2f}x dropped "
                    f">20% below last recorded {last_s:.2f}x "
                    f"(BENCH_dse.json left unchanged)"
                )
    with open(bench_path, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {os.path.normpath(bench_path)}")
    return results


def sim_backends_ab(batch: int = 64, repeats: int = 3):
    """A/B the three self-timed simulator backends on one
    NSGA-II-population-sized batch: ``batch`` feasible Sobel phenotypes
    (MRB_Always ξ, random bindings, CAPS-HMS decode — one shared
    transformed graph, as ``EvaluationEngine.evaluate_batch`` hands the
    batched backends).

      events        per-phenotype event-driven simulate_period loop
      vec_cold      fused-rounds lax backend incl. JIT compilation
      vec_cold2     second *distinct* structure-identical batch — must hit
                    the compiled function (no retrace; asserted via the
                    module trace counter) and land within 1.5x of warm
      vec_warm      compiled + warmed
      pallas_cold / pallas_warm   Pallas actor-step kernel
                    (repro.kernels.sim_step; interpreter mode off-TPU)

    Periods must be identical element-for-element across all three
    backends (the repo-wide parity invariant).  Warm arms are interleaved
    and the per-arm minimum reported (shared-container wall-clock noise
    swamps sequential medians).  BENCH_sim.json keeps a ``history`` list
    — every run appends the previous head — so the bench trajectory
    across PRs is inspectable, and the run *fails* (CI slow job) when a
    warm batched-backend speedup vs events drops below the last recorded
    value by more than 20% (set REPRO_BENCH_NO_GATE=1 to bypass).
    """
    import random
    import time as _time

    from repro.core import paper_architecture, sobel
    from repro.core.binding import CHANNEL_DECISIONS
    from repro.core.caps_hms import decode_via_heuristic
    from repro.core.dse import pipeline_delays
    from repro.core.graph import multicast_actors
    from repro.core.mrb import substitute_mrbs
    from repro.sim import (
        SimConfig,
        batch_simulate_periods,
        simulate_period,
        trace_count,
    )
    from repro.sim import vectorized as _vec

    g, arch = sobel(), paper_architecture()
    gt = pipeline_delays(substitute_mrbs(g, {a: 1 for a in multicast_actors(g)}))
    rng = random.Random(2024)
    cores = sorted(arch.cores)

    def draw_batch(n):
        out = []
        while len(out) < n:
            ba = {
                a: rng.choice(
                    [p for p in cores if gt.actors[a].can_run_on(arch.cores[p].ctype)]
                )
                for a in gt.actors
            }
            cd = {c: rng.choice(CHANNEL_DECISIONS) for c in gt.channels}
            res = decode_via_heuristic(gt, arch, cd, ba)
            if res.feasible:
                out.append(res.schedule)
        return out

    scheds = draw_batch(batch)
    scheds2 = draw_batch(batch)  # distinct values, same structure

    cfg = SimConfig(trace=False)
    results = {}
    periods = {}

    _vec._COMPILED.clear()
    t0 = _time.monotonic()
    periods["vec_first"] = batch_simulate_periods(gt, arch, scheds, cfg)
    results["vec_cold"] = _time.monotonic() - t0
    traces_before = trace_count()
    t0 = _time.monotonic()
    periods["vec_b2"] = batch_simulate_periods(gt, arch, scheds2, cfg)
    results["vec_cold2"] = _time.monotonic() - t0
    assert trace_count() == traces_before, (
        "structure-identical batch retraced the compiled simulator"
    )
    t0 = _time.monotonic()
    periods["pallas_first"] = batch_simulate_periods(
        gt, arch, scheds, cfg, backend="pallas"
    )
    results["pallas_cold"] = _time.monotonic() - t0

    walls = {"events": [], "vec_warm": [], "pallas_warm": []}
    for _ in range(repeats):
        t0 = _time.monotonic()
        periods["events"] = [simulate_period(gt, arch, s, cfg) for s in scheds]
        walls["events"].append(_time.monotonic() - t0)
        t0 = _time.monotonic()
        periods["vec"] = batch_simulate_periods(gt, arch, scheds, cfg)
        walls["vec_warm"].append(_time.monotonic() - t0)
        t0 = _time.monotonic()
        periods["pallas"] = batch_simulate_periods(
            gt, arch, scheds, cfg, backend="pallas"
        )
        walls["pallas_warm"].append(_time.monotonic() - t0)
    for arm, ws in walls.items():
        results[arm] = min(ws)

    assert (
        periods["events"] == periods["vec"] == periods["vec_first"]
        == periods["pallas"] == periods["pallas_first"]
    ), "simulator backends diverged"
    ev_b2 = [simulate_period(gt, arch, s, cfg) for s in scheds2]
    assert ev_b2 == periods["vec_b2"], "second-batch periods diverged"

    speedups = {
        "vectorized": results["events"] / results["vec_warm"],
        "pallas": results["events"] / results["pallas_warm"],
    }
    fast_arm = max(speedups, key=speedups.get)
    cold2_vs_warm = results["vec_cold2"] / results["vec_warm"]
    for arm in ("events", "vec_cold", "vec_cold2", "vec_warm",
                "pallas_cold", "pallas_warm"):
        print(f"arm={arm:12s} wall={results[arm]:.3f}s", flush=True)
    for name, s in speedups.items():
        print(f"speedup {name} warm vs events: {s:.2f}x")
    print(f"fast path: {fast_arm} ({speedups[fast_arm]:.2f}x)")
    print(f"cold2 vs warm (no-retrace second batch): {cold2_vs_warm:.2f}x")
    print(f"periods identical across backends: OK ({batch} phenotypes)")

    bench_path = os.path.join(os.path.dirname(__file__), "..", "BENCH_sim.json")
    prev = None
    try:
        with open(bench_path) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        pass
    history = list(prev.get("history", [])) if prev else []
    if prev:
        history.append(
            {k: prev.get(k) for k in ("arms", "speedups", "periods_identical",
                                      "git_sha", "host")}
        )
    bench = {
        **bench_provenance(),
        "experiment": "sim_backends",
        "config": {"app": "Sobel", "xi": "MRB_Always", "batch": batch,
                   "repeats": repeats, "iterations": cfg.iterations,
                   "max_iterations": cfg.max_iterations},
        "arms": results,
        "speedups": speedups,
        "fast_path": fast_arm,
        "speedup_fast_path_vs_events": speedups[fast_arm],
        "cold2_vs_warm": cold2_vs_warm,
        "periods_identical": True,
        "history": history[-24:],
    }
    # Regression gate (CI slow job): each batched backend must stay within
    # 20% of its last recorded warm speedup.  Checked before the write so
    # a regressed run never replaces the baseline it failed against.
    if prev and prev.get("speedups") and not os.environ.get("REPRO_BENCH_NO_GATE"):
        for name, s in speedups.items():
            last = prev["speedups"].get(name)
            if last and s < 0.8 * last:
                raise SystemExit(
                    f"sim_backends regression: {name} warm speedup {s:.2f}x "
                    f"dropped >20% below last recorded {last:.2f}x "
                    f"(BENCH_sim.json left unchanged)"
                )
    with open(bench_path, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {os.path.normpath(bench_path)}")
    return results


def service_ab(seeds: int = 3, workers: int = 2, repeats: int = 2):
    """A/B the campaign service against the serial local runner on a
    multi-tenant load: a seeded ``2 strategies x seeds`` campaign
    submitted simultaneously by two tenants.

      local_serial   CampaignRunner, jobs=1, in-memory store — the
                     pre-service baseline, run once per tenant (no
                     sharing), so the arm carries the full 2x decode bill
      served         both tenants against one service (ephemeral port,
                     ``workers`` worker processes, shared dedup store):
                     each unique hash is decoded once, the second tenant
                     is pure dedup, and unique decodes fan out across the
                     pool

    Fronts must be bit-identical across arms (the service changes wall
    time only).  Arms are interleaved and the per-arm minimum reported
    (shared-container wall-clock noise swamps sequential medians); the
    served arm gets a fresh store per repeat so every repeat pays its
    decodes.  BENCH_service.json keeps a ``history`` list — every run
    appends the previous head — and the run *fails* (CI slow job) when
    the served-vs-serial speedup drops below the last recorded value by
    more than 20% (set REPRO_BENCH_NO_GATE=1 to bypass).
    """
    import tempfile
    import threading
    import time as _time

    from repro.core import Campaign, CampaignRunner, RunStore
    from repro.scenarios import sample_scenarios
    from repro.service import ServiceClient, make_server

    # A large-size scenario so decode work dominates the service's
    # dispatch/HTTP overhead (~1.7s/cell; the small tiers decode in
    # milliseconds and would benchmark the plumbing, not the scheduling).
    sc = sample_scenarios(seed=0, n=1, families=["stencil_chain"], size="large")[0]
    campaign = Campaign(
        name="service-ab",
        problems=[{"label": "stencil0", "scenario": sc.to_json()}],
        axes={"strategy": ["Reference", "MRB_Explore"],
              "seed": list(range(seeds))},
        explorer="nsga2",
        explorer_params={"population": 24, "offspring": 12, "generations": 8,
                         "track_hypervolume": False},
    )
    tenants = ("alice", "bob")
    n_unique = len({c.spec_hash() for c in campaign.expand()})

    def run_serial():
        t0 = _time.monotonic()
        results = [
            CampaignRunner(campaign, store=RunStore(None)).run()
            for _ in tenants
        ]
        return _time.monotonic() - t0, results[0]

    def run_served():
        root = tempfile.mkdtemp(prefix="service-ab-")
        server, service = make_server(root, port=0, workers=workers)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        host, port = server.server_address[:2]
        client = ServiceClient(f"http://{host}:{port}")
        statuses = {}

        def submit(tenant):
            sub = client.submit(campaign.to_json(), tenant=tenant)
            statuses[tenant] = client.wait(sub["submission_id"], timeout_s=600)

        t0 = _time.monotonic()
        threads = [threading.Thread(target=submit, args=(t,)) for t in tenants]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = _time.monotonic() - t0
        try:
            metrics = client.metrics()
        finally:
            server.shutdown()
            server.server_close()
            service.close()
        assert metrics["counters"]["cells_executed"] == n_unique, (
            f"served arm decoded {metrics['counters']['cells_executed']} "
            f"cells, expected one per unique hash ({n_unique})"
        )
        return wall, statuses, metrics

    # Warm-up: one single-tenant serial run (imports + JIT; every timed
    # run below still pays its decodes cold — fresh stores throughout).
    CampaignRunner(campaign, store=RunStore(None)).run()
    walls = {"local_serial": [], "served": []}
    last_serial = last_served = None
    for _ in range(repeats):
        w, last_serial = run_serial()
        walls["local_serial"].append(w)
        w, last_served, last_metrics = run_served()
        walls["served"].append(w)

    fronts_identical = all(
        [tuple(p) for p in status["report"]["cells"][tag]["front"]]
        == last_serial.front(tag)
        for status in last_served.values()
        for tag in last_serial.cells
    )
    assert fronts_identical, "served fronts diverged from the local runner"

    results = {
        "local_serial": {"wall_s": min(walls["local_serial"]),
                         "decodes": n_unique * len(tenants)},
        "served": {"wall_s": min(walls["served"]),
                   "decodes": n_unique,
                   "dedup_hit_rate": last_metrics["dedup_hit_rate"],
                   "workers": workers},
    }
    speedups = {
        "served_vs_serial": results["local_serial"]["wall_s"]
        / results["served"]["wall_s"],
    }
    for arm, r in results.items():
        print(f"arm={arm:12s} wall={r['wall_s']:.2f}s decodes={r['decodes']}",
              flush=True)
    print(f"speedup served vs local_serial: {speedups['served_vs_serial']:.2f}x "
          f"(dedup_hit_rate={last_metrics['dedup_hit_rate']:.2f})")
    print(f"fronts bit-identical across arms: OK "
          f"({len(tenants)} tenants x {n_unique} cells)")

    bench_path = os.path.join(os.path.dirname(__file__), "..", "BENCH_service.json")
    prev = None
    try:
        with open(bench_path) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        pass
    history = list(prev.get("history", [])) if prev else []
    if prev:
        history.append(
            {k: prev.get(k) for k in ("arms", "speedups", "fronts_identical",
                                      "git_sha", "host")}
        )
    bench = {
        **bench_provenance(),
        "experiment": "service",
        "config": {"family": "stencil_chain", "strategies": 2, "seeds": seeds,
                   "tenants": len(tenants), "workers": workers,
                   "repeats": repeats, "n_unique_cells": n_unique},
        "arms": results,
        "speedups": speedups,
        "fronts_identical": fronts_identical,
        "history": history[-24:],
    }
    # Regression gate (CI slow job): the served speedup must stay within
    # 20% of its last recorded value.  Checked before the write so a
    # regressed run never replaces the baseline it failed against.
    if prev and prev.get("speedups") and not os.environ.get("REPRO_BENCH_NO_GATE"):
        for name, s in speedups.items():
            last = prev["speedups"].get(name)
            if last and s < 0.8 * last:
                raise SystemExit(
                    f"service regression: {name} speedup {s:.2f}x dropped "
                    f">20% below last recorded {last:.2f}x "
                    f"(BENCH_service.json left unchanged)"
                )
    with open(bench_path, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {os.path.normpath(bench_path)}")
    return results


def evo_ab(population: int = 512, offspring: int = 256, generations: int = 5,
           seed: int = 11):
    """A/B the host ``nsga2`` generation loop against the device-resident
    ``jax_nsga2`` (relaxed evaluation) on Sobel / paper24, Reference
    strategy, at population ≥ 512 — the regime the ISSUE targets.

    Per-generation wall times come from ``on_generation`` callback
    timestamps, so both arms are measured by the same clock on exactly the
    loop body (selection + variation + evaluation + truncation), with
    archive/hypervolume post-processing excluded.  The jax arm reports
    cold time-to-first-generation (init evaluation + generation 0, which
    pays jit tracing + XLA compile of the fused step) and warm
    per-generation wall (second explore on the same explorer instance —
    compiled artifacts are cached per instance, so this is the
    steady-state cost).  BENCH_evo.json keeps a ``history`` list — every
    run appends the previous head — and the run *fails* (CI slow job)
    when the warm speedup drops below the last recorded value by more
    than 20% (set REPRO_BENCH_NO_GATE=1 to bypass).
    """
    import time as _time

    from repro.core import (
        ExplorationProblem,
        get_explorer,
        paper_architecture,
        relative_hypervolume,
        sobel,
    )

    g, arch = sobel(), paper_architecture()
    problem = ExplorationProblem(graph=g, arch=arch, strategy="Reference")

    def timed(explorer):
        stamps = []
        t0 = _time.monotonic()
        run = explorer.explore(
            problem,
            on_generation=lambda gen, r: stamps.append(_time.monotonic()),
        )
        # ttfg = init evaluation + generation 0 (where the jax arm pays
        # tracing + XLA compile); diffs = steady-state generation walls.
        ttfg = stamps[0] - t0
        return run, [b - a for a, b in zip(stamps, stamps[1:])], ttfg

    cfg = dict(population=population, offspring=offspring,
               generations=generations, seed=seed, track_hypervolume=False)
    host = get_explorer("nsga2", **cfg)
    dev = get_explorer("jax_nsga2", evaluation="relaxed", **cfg)

    host_run, host_d, host_ttfg = timed(host)
    cold_run, _, cold_ttfg = timed(dev)
    warm_run, warm_d, warm_ttfg = timed(dev)  # same instance: compiled step reused

    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
    host_gen = med(host_d)
    warm_gen = med(warm_d) if warm_d else warm_ttfg
    speedups = {
        "warm_vs_host": host_gen / warm_gen,
        "ttfg_vs_host": host_ttfg / cold_ttfg,
    }
    relhv = relative_hypervolume(warm_run.front, host_run.front)
    results = {
        "host": {"gen_s": host_gen, "ttfg_s": host_ttfg,
                 "front": len(host_run.front),
                 "decodes": host_run.evaluations},
        "jax_cold": {"ttfg_s": cold_ttfg, "front": len(cold_run.front)},
        "jax_warm": {"gen_s": warm_gen, "ttfg_s": warm_ttfg,
                     "front": len(warm_run.front),
                     "relaxed_evaluations":
                         warm_run.meta.get("relaxed_evaluations")},
    }
    print(f"host   gen={host_gen*1e3:8.1f} ms  front={len(host_run.front)}")
    print(f"jax cold ttfg={cold_ttfg*1e3:8.1f} ms (incl. jit + compile)")
    print(f"jax warm gen={warm_gen*1e3:8.1f} ms  front={len(warm_run.front)}")
    print(f"generation throughput: {speedups['warm_vs_host']:.1f}x warm, "
          f"{speedups['ttfg_vs_host']:.1f}x time-to-first-gen; "
          f"relHV(jax, host)={relhv:.3f}")

    bench_path = os.path.join(os.path.dirname(__file__), "..", "BENCH_evo.json")
    prev = None
    try:
        with open(bench_path) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        pass
    history = list(prev.get("history", [])) if prev else []
    if prev:
        history.append({
            "arms": prev.get("arms"),
            "speedups": prev.get("speedups"),
            "relhv": prev.get("relhv"),
            "git_sha": prev.get("git_sha"),
            "host": prev.get("host"),
        })
    bench = {
        **bench_provenance(),
        "experiment": "evo",
        "config": dict(cfg, strategy="Reference", evaluation="relaxed"),
        "arms": results,
        "speedups": speedups,
        "relhv": relhv,
        "history": history[-24:],
    }
    # Regression gate: warm generation-throughput speedup must stay within
    # 20% of the last recorded value; checked before the write so a
    # regressed run never replaces the baseline it failed against.
    if prev and not os.environ.get("REPRO_BENCH_NO_GATE"):
        last_s = (prev.get("speedups") or {}).get("warm_vs_host")
        if last_s and speedups["warm_vs_host"] < 0.8 * last_s:
            raise SystemExit(
                f"evo regression: warm speedup {speedups['warm_vs_host']:.2f}x "
                f"dropped >20% below last recorded {last_s:.2f}x "
                f"(BENCH_evo.json left unchanged)"
            )
    with open(bench_path, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {os.path.normpath(bench_path)}")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", required=True,
                    choices=["ce_mode", "microbatch", "decode_capacity",
                             "dse_cache", "sim_backends", "service", "evo"])
    ap.add_argument("--arch", default="gemma2-9b")
    args = ap.parse_args()

    if args.exp == "dse_cache":
        dse_cache_ab()
        return
    if args.exp == "sim_backends":
        sim_backends_ab()
        return
    if args.exp == "service":
        service_ab()
        return
    if args.exp == "evo":
        evo_ab()
        return

    if args.exp == "ce_mode":
        a = show("gather CE (baseline)", lower_train(args.arch, ce_mode="gather"))
        b = show("onehot CE (vocab-parallel)", lower_train(args.arch, ce_mode="onehot"))
        print(f"collective bytes: {a['collective_bytes']:.3e} -> "
              f"{b['collective_bytes']:.3e} "
              f"({a['collective_bytes']/max(b['collective_bytes'],1):.1f}x)")
    elif args.exp == "microbatch":
        for mb in (1, 4, 16):
            try:
                show(f"microbatches={mb}", lower_train(args.arch, microbatches=mb))
            except Exception as e:
                print(f"microbatches={mb}: {type(e).__name__} {str(e)[:120]}")
    elif args.exp == "decode_capacity":
        from repro.launch.dryrun import run_cell

        rec = run_cell(args.arch, "decode_32k")
        print(json.dumps({k: rec[k] for k in ("memory", "hlo_cost")}, indent=2))


if __name__ == "__main__":
    main()
