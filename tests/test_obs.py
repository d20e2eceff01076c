"""Unified telemetry layer: recorder round-trip, the disabled-path
overhead guard, Chrome-trace export/validation, self-time summaries, the
trace CLI, sim-backend spans, and a deterministic two-tenant
claim-contention trace through the worker-pool scheduler."""
import hashlib
import json
import os
import random
import time

import pytest

from conftest import make_pipelined_sobel, random_decode, tiny_campaign
from repro import obs
from repro.cli import main as cli_main
from repro.core import RunStore
from repro.service import Scheduler, SchedulerConfig


@pytest.fixture()
def obs_env(tmp_path, monkeypatch):
    """Enable telemetry via the environment (so forked workers inherit
    it) into a per-test sink directory; restore the disabled default."""
    d = str(tmp_path / "obs")
    monkeypatch.setenv(obs.OBS_ENV, "1")
    monkeypatch.setenv(obs.OBS_DIR_ENV, d)
    obs.configure(None)  # follow the (patched) environment
    yield d
    obs.shutdown()
    obs.configure(None)


def _spans(summary):
    return {row["name"]: row for row in summary["spans"]}


# ================================================================= recorder
def test_recorder_roundtrip_spans_events_counters(obs_env):
    assert obs.enabled()
    with obs.span("outer.work", label="a") as sp:
        with obs.span("outer.inner"):
            time.sleep(0.01)
        sp.set(extra=7)
    obs.event("outer.marker", k="v")
    obs.counter_add("outer.hits", 2)
    obs.counter_add("outer.hits", 3)
    obs.flush()

    recs = list(obs.iter_records(obs_env))
    by_kind = {}
    for r in recs:
        by_kind.setdefault(r["t"], []).append(r)
    assert len(by_kind["meta"]) == 1
    meta = by_kind["meta"][0]
    assert meta["pid"] > 0 and meta["epoch_ns"] > 0 and meta["host"]

    spans = {r["name"]: r for r in by_kind["span"]}
    assert spans["outer.work"]["attrs"] == {"label": "a", "extra": 7}
    assert spans["outer.work"]["cat"] == "outer"
    assert spans["outer.inner"]["dur"] >= 5_000_000  # slept 10ms
    # Inner closes first but is timestamped inside the outer window.
    assert (
        spans["outer.work"]["ts"]
        <= spans["outer.inner"]["ts"]
        <= spans["outer.work"]["ts"] + spans["outer.work"]["dur"]
    )
    (ev,) = by_kind["event"]
    assert ev["name"] == "outer.marker" and ev["attrs"] == {"k": "v"}
    assert sum(r["value"] for r in by_kind["counter"]) == 5


def test_span_records_exception_and_reraises(obs_env):
    with pytest.raises(ValueError):
        with obs.span("outer.boom"):
            raise ValueError("nope")
    obs.flush()
    (rec,) = [r for r in obs.iter_records(obs_env) if r.get("t") == "span"]
    assert rec["attrs"]["error"] == "ValueError"


def test_configure_beats_environment(tmp_path, monkeypatch):
    monkeypatch.setenv(obs.OBS_ENV, "1")
    monkeypatch.setenv(obs.OBS_DIR_ENV, str(tmp_path / "never"))
    obs.configure(False)
    try:
        assert not obs.enabled()
        with obs.span("x.y"):
            pass
        assert not (tmp_path / "never").exists()
    finally:
        obs.configure(None)


# ============================================================ disabled path
def test_disabled_span_is_a_shared_noop(monkeypatch):
    monkeypatch.delenv(obs.OBS_ENV, raising=False)
    obs.configure(None)
    assert not obs.enabled()
    s1 = obs.span("a.b", k=1)
    s2 = obs.span("c.d")
    assert s1 is s2  # the singleton: no allocation on the disabled path
    with s1 as sp:
        sp.set(anything="ignored")
    obs.event("a.e", k=1)
    obs.counter_add("a.c")


def test_disabled_overhead_bounded(monkeypatch):
    """ISSUE-8 guard: with REPRO_OBS unset, wrapping a realistic work
    body in ``obs.span`` must cost at most a few percent.  The bound is
    deliberately loose (1.25x on the min-of-7) so a noisy CI machine
    cannot flake it, while still catching any accidental allocation,
    lock, or clock read on the disabled path."""
    monkeypatch.delenv(obs.OBS_ENV, raising=False)
    obs.configure(None)
    assert not obs.enabled()

    payload = b"x" * 8192
    n = 2000

    def plain():
        t0 = time.perf_counter()
        for _ in range(n):
            hashlib.sha256(payload).digest()
        return time.perf_counter() - t0

    def spanned():
        t0 = time.perf_counter()
        for i in range(n):
            with obs.span("bench.body", i=i):
                hashlib.sha256(payload).digest()
        return time.perf_counter() - t0

    plain(), spanned()  # warm up
    base = min(plain() for _ in range(7))
    wrapped = min(spanned() for _ in range(7))
    assert wrapped <= base * 1.25, (wrapped, base)


# ============================================================ trace export
def _write_sink(obs_dir, pid, epoch_ns, records, proc="python"):
    obs_dir.mkdir(parents=True, exist_ok=True)
    meta = {"t": "meta", "pid": pid, "host": "testhost", "proc": proc,
            "epoch_ns": epoch_ns, "argv": ["x"]}
    path = obs_dir / f"obs-testhost-{pid}-0.jsonl"
    with open(path, "w") as f:
        for rec in [meta] + records:
            f.write(json.dumps(rec) + "\n")


def test_export_merges_processes_onto_wall_clock(tmp_path):
    """Two sinks with different perf_counter epochs: the exporter must
    use ``epoch_ns + ts`` so the later process's spans land *after* the
    earlier one's even though its raw monotonic ts is smaller."""
    d = tmp_path / "obs"
    ms = 1_000_000
    _write_sink(d, 100, epoch_ns=0, records=[
        {"t": "span", "name": "service.unit", "cat": "service",
         "ts": 0, "dur": 50 * ms, "tid": 1, "attrs": {"tenant": "alice"}},
        {"t": "counter", "name": "service.cells_deduped", "cat": "service",
         "ts": 10 * ms, "tid": 1, "value": 1, "attrs": {}},
        {"t": "counter", "name": "service.cells_deduped", "cat": "service",
         "ts": 20 * ms, "tid": 1, "value": 2, "attrs": {}},
    ], proc="scheduler")
    _write_sink(d, 200, epoch_ns=100 * ms, records=[
        {"t": "span", "name": "engine.decode", "cat": "engine",
         "ts": 5 * ms, "dur": 10 * ms, "tid": 2, "attrs": {}},
        {"t": "event", "name": "service.claim_contention", "cat": "service",
         "ts": 6 * ms, "tid": 2, "attrs": {"tenant": "bob"}},
    ], proc="worker-0")

    out = tmp_path / "trace.json"
    trace = obs.export_chrome_trace(str(d), str(out))
    with open(out) as f:
        assert json.load(f) == trace

    info = obs.validate_chrome_trace(trace)
    assert info["spans"] == 2
    assert info["pids"] == [100, 200]
    assert set(info["cats"]) == {"service", "engine"}
    assert trace["metadata"]["n_processes"] == 2

    by_name = {}
    for e in trace["traceEvents"]:
        by_name.setdefault(e["name"], []).append(e)
    # process_name metadata carries the proc_name and host:pid.
    names = {e["args"]["name"] for e in by_name["process_name"]}
    assert names == {"scheduler (testhost:100)", "worker-0 (testhost:200)"}
    # Wall-clock merge: pid 200's decode starts at epoch 100ms + 5ms.
    (decode,) = by_name["engine.decode"]
    assert decode["ts"] == pytest.approx(105_000)  # µs
    assert decode["dur"] == pytest.approx(10_000)
    # Counters are exported as running totals.
    totals = [e["args"]["cells_deduped"] for e in by_name["service.cells_deduped"]]
    assert totals == [1, 3]
    # Instant markers keep their attrs.
    (mark,) = by_name["service.claim_contention"]
    assert mark["ph"] == "i" and mark["args"]["tenant"] == "bob"
    # The merged stream is time-ordered.
    ts = [e["ts"] for e in trace["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_validate_rejects_malformed_traces():
    with pytest.raises(ValueError, match="traceEvents"):
        obs.validate_chrome_trace({})
    with pytest.raises(ValueError, match="phase"):
        obs.validate_chrome_trace({"traceEvents": [{"ph": "Z"}]})
    with pytest.raises(ValueError, match="dur"):
        obs.validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "s", "ts": 0, "pid": 1}]}
        )


def test_summary_self_time_subtracts_nested_children(tmp_path):
    d = tmp_path / "obs"
    ms = 1_000_000
    _write_sink(d, 1, epoch_ns=0, records=[
        {"t": "span", "name": "service.cell", "cat": "service",
         "ts": 0, "dur": 100 * ms, "tid": 1, "attrs": {}},
        {"t": "span", "name": "engine.decode", "cat": "engine",
         "ts": 10 * ms, "dur": 60 * ms, "tid": 1, "attrs": {}},
        # Same name on another thread: no nesting across threads.
        {"t": "span", "name": "engine.decode", "cat": "engine",
         "ts": 0, "dur": 30 * ms, "tid": 2, "attrs": {}},
        {"t": "counter", "name": "engine.cache_hits", "cat": "engine",
         "ts": 0, "tid": 1, "value": 4, "attrs": {}},
        {"t": "event", "name": "service.queue_wait", "cat": "service",
         "ts": 0, "tid": 1, "attrs": {}},
    ])
    summary = obs.summarize(str(d))
    rows = _spans(summary)
    assert rows["service.cell"]["total_ms"] == pytest.approx(100.0)
    assert rows["service.cell"]["self_ms"] == pytest.approx(40.0)
    assert rows["engine.decode"]["count"] == 2
    assert rows["engine.decode"]["total_ms"] == pytest.approx(90.0)
    assert rows["engine.decode"]["self_ms"] == pytest.approx(90.0)
    assert rows["engine.decode"]["max_ms"] == pytest.approx(60.0)
    assert summary["counters"] == {"engine.cache_hits": 4}
    assert summary["events"] == {"service.queue_wait": 1}

    text = obs.format_summary(summary, top=1)
    assert "service.cell" in text and "engine.decode" not in text.split("\n")[1]
    assert "engine.cache_hits" in text


# ================================================================ trace CLI
def test_trace_cli_export_summary_and_min_cats(tmp_path, capsys):
    d = tmp_path / "obs"
    _write_sink(d, 1, epoch_ns=0, records=[
        {"t": "span", "name": "engine.decode", "cat": "engine",
         "ts": 0, "dur": 1_000_000, "tid": 1, "attrs": {}},
    ])
    out = tmp_path / "t.json"
    rc = cli_main(["trace", "export", "--obs-dir", str(d), "--out", str(out)])
    assert rc == 0
    assert "1 span" in capsys.readouterr().out
    obs.validate_chrome_trace(json.loads(out.read_text()))

    assert cli_main(["trace", "summary", "--obs-dir", str(d)]) == 0
    assert "engine.decode" in capsys.readouterr().out

    # Coverage gate: only one subsystem recorded -> --min-cats 3 fails.
    rc = cli_main(["trace", "export", "--obs-dir", str(d),
                   "--out", str(out), "--min-cats", "3"])
    captured = capsys.readouterr()
    assert rc == 1 and "engine" in captured.err

    # Empty obs dir is a one-line CLI error, not a traceback.
    rc = cli_main(["trace", "export", "--obs-dir", str(tmp_path / "empty")])
    captured = capsys.readouterr()
    assert rc == 2
    assert captured.err.startswith("repro: error: ")
    assert "Traceback" not in captured.err


# ================================================================ sim spans
def test_sim_backends_record_compile_execute_spans(obs_env):
    """The batched simulator's execution is a ``sim.execute`` span, the
    event simulator's a ``sim.events`` span, and JAX's own compile event
    for a freshly built simulator is a ``jax.compiles`` count."""
    gt, arch = make_pipelined_sobel()
    res = random_decode(gt, arch, random.Random(0))

    import jax  # noqa: F401  (the compile listener needs JAX imported)

    from repro.sim import SimConfig, batch_simulate, simulate
    from repro.sim import vectorized

    cfg = SimConfig(trace=False)
    vectorized._COMPILED.clear()  # a fresh jit wrapper: its first call compiles
    batch_simulate(gt, arch, [res.schedule], cfg)
    simulate(gt, arch, res.schedule, cfg)
    obs.flush()

    summary = obs.summarize(obs_env)
    rows = _spans(summary)
    assert "sim.execute" in rows  # vectorized backend ran
    assert rows["sim.execute"]["count"] >= 1
    assert "sim.events" in rows  # exact backend ran
    assert summary["counters"].get("sim.cache_builds", 0) >= 1
    assert summary["counters"].get("jax.compiles", 0) >= 1
    assert {n for n in rows if n.startswith("sim.")} == {"sim.execute", "sim.events"}


# ========================================================== engine counters
def _sobel_decode_counting_successes(monkeypatch):
    """One CAPS-HMS decode of the pipelined Sobel; returns it with the
    number of actors and of the period probes that found a schedule."""
    import importlib

    from repro.core.binding import CHANNEL_DECISIONS

    # The module, not the function that repro.core re-exports under its name.
    caps_mod = importlib.import_module("repro.core.caps_hms")

    successes = []
    probe = caps_mod.caps_hms

    def counted(*args, **kwargs):
        times = probe(*args, **kwargs)
        successes.append(times is not None)
        return times

    monkeypatch.setattr(caps_mod, "caps_hms", counted)
    gt, arch = make_pipelined_sobel()
    rng = random.Random(0)
    cores = sorted(arch.cores)
    ba = {
        a: rng.choice([p for p in cores if gt.actors[a].can_run_on(arch.cores[p].ctype)])
        for a in gt.actors
    }
    cd = {c: rng.choice(CHANNEL_DECISIONS) for c in gt.channels}
    res = caps_mod.decode_via_heuristic(gt, arch, cd, ba)
    return res, len(gt.actors), sum(successes)


def test_caps_hms_counts_attempts_and_candidates(obs_env, monkeypatch):
    res, n_actors, n_ok = _sobel_decode_counting_successes(monkeypatch)
    obs.flush()
    counters = obs.summarize(obs_env)["counters"]
    assert res.feasible and res.periods_tried >= 2 and n_ok >= 1
    assert counters["caps_hms.attempts"] == res.periods_tried
    assert counters["caps_hms.candidates"] >= n_actors * n_ok


def test_caps_hms_counters_record_nothing_when_off(tmp_path, monkeypatch):
    monkeypatch.delenv(obs.OBS_ENV, raising=False)
    monkeypatch.setenv(obs.OBS_DIR_ENV, str(tmp_path / "never"))
    obs.configure(None)
    try:
        assert not obs.enabled()
        res, _, _ = _sobel_decode_counting_successes(monkeypatch)
        obs.flush()
        assert res.periods_tried >= 1
        assert not (tmp_path / "never").exists()
    finally:
        obs.configure(None)


# ========================================================= profiler bridge
def _profile(fn):
    """Run ``fn`` under the profiler; the host events that are not ops, and
    the op events, of the trace it wrote, as (name, start, end)."""
    import glob
    import os
    import tempfile

    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
        notes, ops = [], []
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    rec = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    (ops if "hlo_op" in dict(ev.stats) else notes).append(rec)
    return notes, ops


def test_span_is_a_profiler_annotation_on_the_ops_clock(obs_env):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()

    def work():
        with obs.span("explorer.generation", gen=7):
            with obs.span("evo.execute"):
                f(x).block_until_ready()

    notes, ops = _profile(work)
    (gen,) = [n for n in notes if n[0] == "explorer.generation"]
    (ex,) = [n for n in notes if n[0] == "evo.execute"]
    assert gen[1] <= ex[1] and ex[2] <= gen[2]
    # The jitted call's ops ran inside the span, on the profile's one clock.
    inside = [op for op in ops if ex[1] <= op[1] and op[2] <= ex[2]]
    assert inside and any(op[0].startswith("dot") for op in inside)
    obs.flush()
    spans = [r for r in obs.iter_records(obs_env) if r.get("t") == "span"]
    assert {s["name"] for s in spans} >= {"explorer.generation", "evo.execute"}


def test_disabled_span_makes_no_annotation(monkeypatch):
    import jax

    monkeypatch.delenv(obs.OBS_ENV, raising=False)
    obs.configure(False)
    try:
        def work():
            with obs.span("evo.execute"):
                jax.numpy.ones(4).block_until_ready()

        notes, _ = _profile(work)
    finally:
        obs.configure(None)
    assert not [n for n in notes if n[0] == "evo.execute"]


def test_recorder_never_imports_jax(tmp_path):
    """A process that never imported JAX (a pool worker) records spans,
    on or off, without importing it."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from repro import obs\n"
        "for on in (False, True):\n"
        f"    obs.configure(on, {str(tmp_path)!r})\n"
        "    with obs.span('explorer.generation', gen=1):\n"
        "        with obs.span('evo.execute'):\n"
        "            pass\n"
        "    obs.counter_add('a.b')\n"
        "obs.shutdown()\n"
        "assert 'jax' not in sys.modules, 'repro.obs imported jax'\n"
        "print(sum(1 for r in obs.iter_records() if r.get('t') == 'span'))\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         check=True, env=dict(os.environ, PYTHONPATH="src"))
    assert out.stdout.split() == ["2"]


def test_compile_listener_follows_configure(obs_env):
    """JAX's compile events are recorded while telemetry is on, and not
    after ``configure(False)``."""
    import jax
    import jax.numpy as jnp

    def compiles():
        obs.flush()
        return sum(r["value"] for r in obs.iter_records(obs_env)
                   if r.get("t") == "counter" and r["name"] == "jax.compiles")

    obs.configure(None)
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(5)).block_until_ready()
    seen = compiles()
    assert seen >= 1
    obs.configure(False)
    jax.jit(lambda x: x * 5.0 - 2.0)(jnp.ones(5)).block_until_ready()
    obs.configure(None)
    assert compiles() == seen
    jax.jit(lambda x: x * 7.0)(jnp.ones(5)).block_until_ready()
    assert compiles() > seen


def test_enabled_telemetry_keeps_other_scope_names_out_of_the_cache(tmp_path):
    """JAX's persistent cache keys a program without its op metadata by
    default, so the same program with its device parts named differently
    loads the executable compiled under the old names.  While telemetry is on the
    key holds the metadata, and the program compiles under its own."""
    import subprocess
    import sys

    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro import obs\n"
        f"jax.config.update('jax_compilation_cache_dir', {str(tmp_path / 'cache')!r})\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)\n"
        "def step(scoped):\n"
        "    def f(x):\n"
        "        if not scoped:\n"
        "            return jnp.sin(x) * 2.0 + 1.0\n"
        "        with jax.named_scope('rank'):\n"
        "            return jnp.sin(x) * 2.0 + 1.0\n"
        "    return f\n"
        "def names(f):\n"
        "    return jax.jit(f).lower(jnp.ones(8)).compile().as_text().count('rank')\n"
        "obs.configure(False)\n"
        "plain_names = names(step(False))\n"
        "stale = names(step(True))\n"
        f"obs.configure(True, {str(tmp_path / 'obs')!r})\n"
        "fresh = names(step(True))\n"
        "obs.configure(False)\n"
        "print(plain_names, stale, fresh, jax.config.jax_compilation_cache_include_metadata_in_key)\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         check=True, env=dict(os.environ, PYTHONPATH="src"))
    plain_names, stale, fresh, key_after = out.stdout.split()
    assert (plain_names, stale, key_after) == ("0", "0", "False")
    assert int(fresh) > 0


# =============================================== two-tenant contention trace
def test_two_tenant_contention_trace_is_deterministic(obs_env, tmp_path):
    """The ISSUE-8 acceptance trace, made deterministic: a ghost owner
    pre-claims every cell hash, so both tenants' workers *must* hit
    claim contention and park; after the claim TTL one worker inherits
    each cell (stale takeover) and the other resolves by dedup.  The
    merged trace then provably contains scheduler/worker spans, per-cell
    decode spans, and contention events from both tenants."""
    store = RunStore(str(tmp_path / "cells"))
    cells = tiny_campaign().expand()
    for c in cells:
        assert store.claim(c.spec_hash(), "ghost")

    cfg = SchedulerConfig(claim_ttl_s=4.0)
    sched = Scheduler(store, workers=2, config=cfg).start()
    try:
        sched.submit("a", "alice", [cells])
        sched.submit("b", "bob", [cells])
        assert sched.wait("a", timeout_s=600) and sched.wait("b", timeout_s=600)
        assert sched.campaign_state("a")["errors"] == []
        assert sched.campaign_state("b")["errors"] == []
    finally:
        sched.close()

    trace = obs.export_chrome_trace(obs_env, str(tmp_path / "trace.json"))
    info = obs.validate_chrome_trace(trace)
    # Coverage across subsystems (the CI smoke asserts the same floor).
    assert {"service", "engine", "explorer"} <= set(info["cats"])
    # Scheduler process + 2 workers on one merged timeline.
    assert len(info["pids"]) >= 3

    events = trace["traceEvents"]
    names = {e["name"] for e in events}
    assert {"service.unit", "service.cell", "service.claim_wait",
            "engine.decode", "service.queue_wait"} <= names

    contention = [e for e in events if e["name"] == "service.claim_contention"]
    assert {e["args"]["tenant"] for e in contention} == {"alice", "bob"}
    takeovers = [e for e in events if e["name"] == "service.stale_takeover"]
    assert len(takeovers) == len(cells)  # ghost never finishes; one per cell
    waits = [e for e in events if e["name"] == "service.claim_wait"]
    outcomes = [w["args"]["outcome"] for w in waits]
    assert set(outcomes) <= {"dedup", "stale_takeover"}
    assert outcomes.count("stale_takeover") == len(cells)
    # Cell spans carry tenant identity from both submissions.
    cell_spans = [e for e in events if e["name"] == "service.cell"]
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in cell_spans)
    assert len(cell_spans) == len(cells)  # each hash decoded exactly once

    # Worker processes announce themselves on the timeline.
    proc_names = {
        e["args"]["name"] for e in events if e["name"] == "process_name"
    }
    assert any("worker-0" in n for n in proc_names)
    assert any("worker-1" in n for n in proc_names)

    # The self-time summary sees the same story.
    summary = obs.summarize(obs_env)
    assert summary["counters"]["service.cells_deduped"] == len(cells)
    assert summary["n_processes"] >= 3
