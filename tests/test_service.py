"""Campaign service: scheduler scoring/fairness, claim-based dedup,
worker supervision (SIGKILL retry, bounded retries), the HTTP/JSON API
end-to-end (concurrent tenants, streaming events, metrics, Prometheus
exposition, event pagination), and the CLI error paths."""
import json
import os
import re
import signal
import threading
import time
import urllib.request

import pytest

from conftest import TINY, tiny_campaign
from repro.cli import main as cli_main
from repro.core import CampaignRunner, RunStore
from repro.core.runstore import canonical_json
from repro.service import (
    CampaignView,
    GlobalStore,
    Scheduler,
    SchedulerConfig,
    ServiceClient,
    ServiceError,
    make_server,
)
from repro.service.scheduler import CELL_DELAY_ENV, WorkUnit


def _wait_for(predicate, timeout_s=60.0, interval_s=0.05):
    deadline = time.monotonic() + timeout_s
    while not predicate():
        assert time.monotonic() < deadline, "timed out waiting for condition"
        time.sleep(interval_s)


def _unit(tenant, n_cells, priority=0, enqueued_at=None):
    return WorkUnit(
        unit_id=f"{tenant}-{n_cells}-{priority}",
        campaign_id=f"c-{tenant}",
        tenant=tenant,
        cells=[{"i": i} for i in range(n_cells)],
        priority=priority,
        enqueued_at=time.monotonic() if enqueued_at is None else enqueued_at,
    )


# =================================================================== scoring
def test_scheduler_prefers_big_groups_then_ages_small_ones():
    sched = Scheduler(RunStore(None), workers=0)
    small_old = _unit("t", 1, enqueued_at=time.monotonic() - 60)
    big_new = _unit("t", 8)
    with sched._lock:
        sched._queue.extend([big_new, small_old])
        # 60s of waiting at aging_rate=2 beats a 7-cell size edge.
        assert sched._pick_unit_locked() is small_old
        assert sched._pick_unit_locked() is big_new

    sched2 = Scheduler(RunStore(None), workers=0)
    small, big = _unit("t", 1), _unit("t", 8)
    with sched2._lock:
        sched2._queue.extend([small, big])
        assert sched2._pick_unit_locked() is big  # same age: big first


def test_scheduler_tenant_priority_dominates_size():
    sched = Scheduler(RunStore(None), workers=0)
    big_low = _unit("free", 50, priority=0)
    small_high = _unit("paid", 1, priority=1)
    with sched._lock:
        sched._queue.extend([big_low, small_high])
        assert sched._pick_unit_locked() is small_high


def test_scheduler_fair_share_passes_over_saturating_tenant():
    sched = Scheduler(RunStore(None), workers=2)  # quota = 2//2 = 1 each
    with sched._lock:
        sched._tenant("hog")["running_units"] = 2   # hog owns the pool
        sched._tenant("mouse")["running_units"] = 0
        hog_unit = _unit("hog", 50)
        mouse_unit = _unit("mouse", 1)
        sched._queue.extend([hog_unit, mouse_unit])
        assert sched._pick_unit_locked() is mouse_unit
        # Nobody else waiting: the hog may keep the pool saturated.
        assert sched._pick_unit_locked() is hog_unit


def test_scheduler_backoff_delays_retried_unit():
    sched = Scheduler(RunStore(None), workers=0)
    delayed = _unit("t", 4)
    delayed.not_before = time.monotonic() + 60
    ready = _unit("t", 1)
    with sched._lock:
        sched._queue.extend([delayed, ready])
        assert sched._pick_unit_locked() is ready
        assert sched._pick_unit_locked() is None  # delayed not eligible yet


# ==================================================================== dedup
def test_inline_scheduler_dedups_across_campaigns():
    """Two campaigns expanding to the same cells, one store: the second
    campaign is pure dedup — zero additional decodes."""
    store = RunStore(None)
    events = []
    sched = Scheduler(store, workers=0, on_event=events.append)
    cells = tiny_campaign().expand()
    sched.submit("c1", "alice", [cells])
    assert sched.wait("c1", timeout_s=300)
    sched.submit("c2", "bob", [cells])
    assert sched.wait("c2", timeout_s=300)
    m = sched.metrics()
    assert m["counters"]["cells_executed"] == len(cells)
    assert m["counters"]["cells_deduped"] == len(cells)
    assert m["dedup_hit_rate"] == pytest.approx(0.5)
    assert m["tenants"]["bob"]["executed_cells"] == 0
    types = [e["type"] for e in events]
    assert types.count("cell_done") == len(cells)
    assert types.count("cell_dedup") == len(cells)


def test_worker_pool_decodes_each_hash_exactly_once(tmp_path):
    """Two tenants submit overlapping campaigns into one worker pool at
    the same time; the claim protocol serializes per-hash decode work so
    every unique hash is decoded exactly once."""
    store = RunStore(str(tmp_path / "cells"))
    sched = Scheduler(store, workers=2).start()
    try:
        cells = tiny_campaign().expand()
        # share_engines=False -> one unit per cell, maximal claim contention.
        units_a = [[c] for c in cells]
        units_b = [[c] for c in cells]
        sched.submit("a", "alice", units_a)
        sched.submit("b", "bob", units_b)
        assert sched.wait("a", timeout_s=300) and sched.wait("b", timeout_s=300)
        m = sched.metrics()
        assert m["counters"]["cells_executed"] == len(cells)
        assert m["counters"]["cells_deduped"] == len(cells)
        for c in cells:
            assert store.try_load_cell(c.spec_hash()) is not None
    finally:
        sched.close()


def test_pooled_workers_refuse_device_explorer_cells(tmp_path):
    """Pool workers are host-only processes (one process holds the chip):
    a jax_nsga2 cell sent to a pool fails fast, naming jobs=1, instead of
    silently running on the CPU; the device-owning process accepts it."""
    camp = tiny_campaign(explorer="jax_nsga2", share_engines=False)
    cells = camp.expand()
    with pytest.raises(ValueError, match="jobs=1"):
        CampaignRunner(camp, root=str(tmp_path), jobs=2).run()
    with pytest.raises(ValueError, match="jobs=1"):
        Scheduler(RunStore(None), workers=2).submit("c", "t", [[c] for c in cells])
    assert Scheduler(RunStore(None), workers=0).submit("c", "t", [cells]) == 1


# ============================================================== supervision
def test_sigkilled_worker_unit_retried_to_completion(tmp_path, monkeypatch):
    """SIGKILL a worker mid-cell: the supervisor respawns it, releases
    its claims, requeues the in-flight unit with backoff, and the
    campaign still completes with valid artifacts."""
    monkeypatch.setenv(CELL_DELAY_ENV, "1.0")
    store = RunStore(str(tmp_path / "cells"))
    events = []
    cfg = SchedulerConfig(
        heartbeat_timeout_s=10.0, claim_ttl_s=5.0, backoff_base_s=0.1
    )
    sched = Scheduler(store, workers=1, config=cfg, on_event=events.append).start()
    try:
        cells = tiny_campaign().expand()
        sched.submit("c1", "alice", [cells])
        _wait_for(lambda: any(e["type"] == "cell_started" for e in events))
        os.kill(sched.worker_pids()[0], signal.SIGKILL)
        assert sched.wait("c1", timeout_s=300)
        state = sched.campaign_state("c1")
        m = sched.metrics()
    finally:
        sched.close()
    assert state["errors"] == []
    # The retried unit may legitimately dedup a cell its first incarnation
    # finished before the kill; executed ∪ deduped must cover the campaign.
    assert set(state["executed"]) | set(state["deduped"]) == {
        c.spec_hash() for c in cells
    }
    assert m["counters"]["retries"] >= 1
    assert m["counters"]["worker_restarts"] >= 1
    types = {e["type"] for e in events}
    assert {"worker_restart", "unit_retry"} <= types
    for c in cells:  # artifacts intact despite the kill
        art = store.try_load_cell(c.spec_hash())
        assert art is not None and art["spec_hash"] == c.spec_hash()


def test_retry_budget_exhausted_marks_unit_failed(tmp_path, monkeypatch):
    """With max_retries=0 a single worker death fails the unit — bounded
    retry, no infinite respawn loop."""
    monkeypatch.setenv(CELL_DELAY_ENV, "2.0")
    store = RunStore(str(tmp_path / "cells"))
    events = []
    cfg = SchedulerConfig(heartbeat_timeout_s=10.0, max_retries=0)
    sched = Scheduler(store, workers=1, config=cfg, on_event=events.append).start()
    try:
        sched.submit("c1", "alice", [tiny_campaign().expand()])
        _wait_for(lambda: any(e["type"] == "cell_started" for e in events))
        os.kill(sched.worker_pids()[0], signal.SIGKILL)
        assert sched.wait("c1", timeout_s=120)
        state = sched.campaign_state("c1")
    finally:
        sched.close()
    assert state["done"] and len(state["errors"]) == 1
    assert "worker died" in state["errors"][0]
    assert any(e["type"] == "unit_failed" for e in events)


# ============================================================= global store
def test_campaign_view_shares_cells_isolates_manifests(tmp_path):
    gs = GlobalStore(str(tmp_path / "svc"))
    a, b = gs.view("alice--camp"), gs.view("bob--camp")
    assert isinstance(a, CampaignView)
    a.save_cell("a" * 64, {"x": 1})
    assert b.try_load_cell("a" * 64) == {"x": 1}  # cells are shared
    a.write_manifest({"campaign": {"name": "A"}, "cells": [{"spec_hash": "a" * 64}]})
    b.write_manifest({"campaign": {"name": "B"}, "cells": []})
    assert a.read_manifest()["campaign"]["name"] == "A"  # manifests are not
    assert b.read_manifest()["campaign"]["name"] == "B"
    # completed() is scoped by the submission's manifest.
    assert a.completed() == ["a" * 64]
    assert b.completed() == []
    assert gs.stats() == {"unique_cells": 1, "submissions": 2}
    assert gs.submissions() == ["alice--camp", "bob--camp"]


# ================================================================= HTTP API
@pytest.fixture()
def served(tmp_path):
    server, service = make_server(str(tmp_path / "svc"), workers=2)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    host, port = server.server_address[:2]
    try:
        yield ServiceClient(f"http://{host}:{port}")
    finally:
        server.shutdown()
        server.server_close()
        service.close()


def test_http_concurrent_tenants_dedup_and_bit_identical_reports(served):
    """The ISSUE-7 acceptance path: two concurrent clients submit the
    same campaign; each unique hash is decoded exactly once (dedup rate
    at /metrics) and both served reports are bit-identical to a local
    CampaignRunner run."""
    camp = tiny_campaign()
    results = {}

    def submit(tenant):
        sub = served.submit(camp.to_json(), tenant=tenant)
        results[tenant] = served.wait(sub["submission_id"], timeout_s=300)

    threads = [threading.Thread(target=submit, args=(t,)) for t in ("alice", "bob")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    local = CampaignRunner(tiny_campaign(), store=RunStore(None)).run()
    for tenant in ("alice", "bob"):
        report = results[tenant]["report"]
        assert results[tenant]["done"]
        assert report["n_completed"] == report["n_cells"] == 2
        for tag in local.cells:
            got = [tuple(p) for p in report["cells"][tag]["front"]]
            assert got == local.front(tag), (tenant, tag)
        # Identical serialized report rows modulo wall time.
        for tag, row in report["cells"].items():
            assert row["spec_hash"] == local.cells[tag]["spec_hash"]

    m = served.metrics()
    assert m["counters"]["cells_executed"] == 2   # one decode per unique hash
    assert m["counters"]["cells_deduped"] == 2
    assert m["dedup_hit_rate"] == pytest.approx(0.5)
    assert set(m["tenants"]) == {"alice", "bob"}
    assert m["queue_depth"] == 0
    assert "backend_timing" in m and m["store"]["unique_cells"] == 2


def test_http_submit_is_idempotent_resume(served):
    camp = tiny_campaign()
    first = served.submit(camp.to_json(), tenant="alice")
    served.wait(first["submission_id"], timeout_s=300)
    again = served.submit(camp.to_json(), tenant="alice")
    assert again["submission_id"] == first["submission_id"]
    assert again["n_pending"] == 0 and again["n_resumed"] == 2
    status = served.status(first["submission_id"])
    assert status["done"] and status["report"]["missing"] == []


def test_http_event_stream_replays_and_terminates(served):
    camp = tiny_campaign()
    sub = served.submit(camp.to_json(), tenant="alice")
    served.wait(sub["submission_id"], timeout_s=300)
    events = list(served.events(sub["submission_id"]))
    types = [e["type"] for e in events]
    assert types[0] == "submitted"
    assert types.count("cell_done") + types.count("cell_dedup") == 2
    assert all(e["campaign_id"] == sub["submission_id"] for e in events[1:])
    started = [e for e in events if e["type"] == "cell_started"]
    assert all("tag" in e and "spec_hash" in e for e in started)


def test_http_error_paths(served):
    with pytest.raises(ServiceError) as e:
        served.status("nope--missing")
    assert e.value.code == 404
    with pytest.raises(ServiceError) as e:
        served.submit({"name": "broken"})  # no problems -> invalid spec
    assert e.value.code == 400
    with pytest.raises(ServiceError) as e:
        served._request("/campaigns", {"campaign": "not-a-dict"})
    assert e.value.code == 400
    assert served.healthz() == {"ok": True}
    assert served.submissions() == []


# ==================================================== observability surface
@pytest.fixture()
def served_inline(tmp_path):
    """A served instance in inline mode (workers=0): submissions queue
    until ``service.scheduler.drain()`` runs them in-process — cheap and
    deterministic for surface tests that don't need a worker pool."""
    server, service = make_server(str(tmp_path / "svc"), workers=0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    host, port = server.server_address[:2]
    client = ServiceClient(f"http://{host}:{port}")
    try:
        yield client, service
    finally:
        server.shutdown()
        server.server_close()
        service.close()


def test_metrics_json_schema_pinned(served_inline):
    """The /metrics JSON shape is an API: dashboards, perf_ab, and the
    Prometheus mapping in repro.obs.prom all consume it.  Pin every key
    so a rename shows up here instead of in a silent scrape gap."""
    client, service = served_inline
    sub = client.submit(tiny_campaign().to_json(), tenant="alice")
    service.scheduler.drain()
    m = client.metrics()
    assert set(m) == {
        "uptime_s", "store", "queue_depth", "inflight", "counters",
        "dedup_hit_rate", "tenants", "backend_timing", "workers", "campaigns",
    }
    assert m["uptime_s"] > 0
    assert set(m["store"]) == {"unique_cells", "submissions"}
    assert set(m["counters"]) == {
        "units_submitted", "units_done", "units_failed", "retries",
        "worker_restarts", "cells_executed", "cells_deduped",
        "deadline_cancels",
    }
    assert set(m["tenants"]["alice"]) == {
        "queued_units", "running_units", "submitted_cells",
        "executed_cells", "deduped_cells", "wall_s",
    }
    assert m["backend_timing"], "a drained campaign must report timing"
    for stats in m["backend_timing"].values():
        assert set(stats) == {"cells", "wall_s_total", "wall_s_mean"}
    row = m["campaigns"][sub["submission_id"]]
    assert set(row) == {"pending_units", "tenant", "executed", "deduped", "errors"}
    assert m["workers"] == []  # inline mode has no worker processes
    assert m["queue_depth"] == 0 and m["inflight"] == 0


_PROM_LINE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (\S+)$")


def _parse_prom(text):
    """Parse exposition text into ``{(name, labels): value}`` + declared
    types, asserting the format invariants a real scraper relies on."""
    samples, types = {}, {}
    for line in text.rstrip("\n").split("\n"):
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ")
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        match = _PROM_LINE.match(line)
        assert match, f"malformed sample line: {line!r}"
        name, labels_s, value = match.groups()
        labels = ()
        if labels_s:
            labels = tuple(sorted(
                (kv.split("=", 1)[0], kv.split("=", 1)[1].strip('"'))
                for kv in labels_s.split(",")
            ))
        assert name in types, f"sample {name} missing TYPE declaration"
        key = (name, labels)
        assert key not in samples, f"duplicate sample {key}"
        samples[key] = float(value)
    return samples, types


def test_prometheus_exposition_cross_checks_json(served_inline):
    """Accept: text/plain serves Prometheus exposition whose every
    sample matches the JSON endpoint — the two surfaces are one source."""
    client, service = served_inline
    camp = tiny_campaign()
    client.submit(camp.to_json(), tenant="alice")
    client.submit(camp.to_json(), tenant="bob")  # dedups against alice
    service.scheduler.drain()

    m = client.metrics()
    text = client.metrics_text()
    samples, types = _parse_prom(text)

    assert samples[("repro_queue_depth", ())] == m["queue_depth"]
    assert samples[("repro_inflight", ())] == m["inflight"]
    assert samples[("repro_dedup_hit_rate", ())] == pytest.approx(m["dedup_hit_rate"])
    assert m["dedup_hit_rate"] == pytest.approx(0.5)
    assert samples[("repro_campaigns", ())] == len(m["campaigns"]) == 2
    assert samples[("repro_uptime_seconds", ())] >= m["uptime_s"]

    for name, v in m["counters"].items():
        assert samples[(f"repro_{name}_total", ())] == v
        assert types[f"repro_{name}_total"] == "counter"
    for key, v in m["store"].items():
        assert samples[(f"repro_store_{key}", ())] == v
    for tenant, stats in m["tenants"].items():
        for key, v in stats.items():
            assert samples[(f"repro_tenant_{key}", (("tenant", tenant),))] == pytest.approx(v)
    for backend, stats in m["backend_timing"].items():
        lbl = (("backend", backend),)
        assert samples[("repro_backend_cells_total", lbl)] == stats["cells"]
        assert samples[("repro_backend_wall_seconds_total", lbl)] == pytest.approx(
            stats["wall_s_total"]
        )
    assert samples[("repro_workers_alive", ())] == 0  # inline: no pool
    assert samples[("repro_workers_total", ())] == 0

    # Content negotiation: the scrape target advertises the exposition
    # version; a client that also accepts JSON keeps getting JSON.
    req = urllib.request.Request(
        client.base_url + "/metrics", headers={"Accept": "text/plain"}
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert resp.headers["Content-Type"].startswith("text/plain; version=0.0.4")
    req = urllib.request.Request(
        client.base_url + "/metrics",
        headers={"Accept": "text/plain, application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert resp.headers["Content-Type"].startswith("application/json")
        json.loads(resp.read().decode())


def test_events_since_pagination_boundaries(served_inline):
    client, service = served_inline
    sid = client.submit(tiny_campaign().to_json(), tenant="alice")["submission_id"]
    service.scheduler.drain()

    full, end, done = service.events_since(sid, 0, timeout_s=0)
    assert done and end == len(full) and len(full) >= 3
    assert full[0]["type"] == "submitted"

    # A middle page replays the exact suffix and lands on the same end.
    page, nxt, done = service.events_since(sid, 2, timeout_s=0)
    assert page == full[2:] and nxt == end and done
    # since == end: empty page, index unchanged (the poll position).
    page, nxt, done = service.events_since(sid, end, timeout_s=0)
    assert page == [] and nxt == end and done
    # since past the end is echoed back, not clamped — a stale client
    # keeps a stable cursor instead of silently re-reading the tail.
    page, nxt, done = service.events_since(sid, end + 5, timeout_s=0)
    assert page == [] and nxt == end + 5 and done
    # Unknown submission: no events, and "done" (nothing is scheduled).
    page, nxt, done = service.events_since("ghost--none", 0, timeout_s=0)
    assert page == [] and nxt == 0 and done

    # The HTTP stream honours ?since=N: replay from 1 drops "submitted"
    # and still terminates with the (consumed) stream_end line.
    streamed = list(client.events(sid, since=1))
    assert streamed == full[1:]
    assert list(client.events(sid, since=end)) == []


# ================================================================ CLI seam
def test_cli_submit_status_against_served_instance(tmp_path, capsys):
    server, service = make_server(str(tmp_path / "svc"), workers=0)
    # workers=0 keeps this test single-process; submissions run inline
    # in a drain thread.
    threading.Thread(target=server.serve_forever, daemon=True).start()
    host, port = server.server_address[:2]
    url = f"http://{host}:{port}"
    drain = threading.Thread(target=service.scheduler.drain, daemon=True)
    spec = tmp_path / "spec.json"
    spec.write_text(tiny_campaign().dumps())
    try:
        rc = cli_main(["campaign", "submit", str(spec), "--url", url, "--no-wait",
                       "--tenant", "cli"])
        out = capsys.readouterr().out
        assert rc == 0 and "submitted cli--" in out
        drain.start()
        drain.join(timeout=300)
        sid = out.split("submitted ")[1].split(":")[0]
        assert cli_main(["campaign", "status", sid, "--url", url]) == 0
        assert "2/2 cells" in capsys.readouterr().out
        assert cli_main(["campaign", "metrics", "--url", url]) == 0
        assert '"dedup_hit_rate"' in capsys.readouterr().out
    finally:
        server.shutdown()
        server.server_close()
        service.close()


def test_cli_one_line_errors(tmp_path, capsys):
    """Satellite: malformed spec, unknown decoder, nonexistent path each
    exit non-zero with a single-line diagnostic, no traceback."""
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    cases = [["campaign", "run", str(bad)]]

    sc = tiny_campaign().problems[0]["scenario"]
    unk = tmp_path / "unk.json"
    unk.write_text(json.dumps({
        "name": "unk",
        "problems": [{"label": "p", "scenario": sc}],
        "axes": {"decoder": ["definitely_not_a_decoder"]},
        "explorer_params": dict(TINY),
    }))
    cases.append(["campaign", "run", str(unk), "--root", str(tmp_path / "r")])
    cases.append(["campaign", "run", str(tmp_path / "missing.json")])

    for argv in cases:
        rc = cli_main(argv)
        captured = capsys.readouterr()
        assert rc != 0, argv
        assert captured.err.startswith("repro: error: "), argv
        assert captured.err.strip().count("\n") == 0, argv  # one line
        assert "Traceback" not in captured.err + captured.out, argv
    rc = cli_main(["campaign", "run", str(unk), "--root", str(tmp_path / "r")])
    captured = capsys.readouterr()
    assert "definitely_not_a_decoder" in captured.err


def test_cli_submit_unreachable_service_one_line(tmp_path, capsys):
    spec = tmp_path / "spec.json"
    spec.write_text(tiny_campaign().dumps())
    rc = cli_main(["campaign", "submit", str(spec),
                   "--url", "http://127.0.0.1:1", "--no-wait"])
    captured = capsys.readouterr()
    # Unreachable is transient (the client already retried): exit 3, the
    # "retry later" code, distinct from permanent errors' exit 2.
    assert rc == 3
    assert captured.err.startswith("repro: error: ")
    assert "Traceback" not in captured.err


# ======================================================== local == service
def test_local_runner_and_service_share_artifact_bytes(tmp_path):
    """A cell artifact produced by the served scheduler is byte-identical
    to the one the local CampaignRunner writes for the same spec hash —
    the dedup story depends on it."""
    camp = tiny_campaign()
    local_store = RunStore(str(tmp_path / "local"))
    CampaignRunner(camp, store=local_store).run()

    gs = GlobalStore(str(tmp_path / "svc"))
    view = gs.view("t--x")
    view.write_manifest(camp.manifest())
    sched = Scheduler(gs.cells, workers=0)
    sched.submit("t--x", "t", [camp.expand()])
    assert sched.wait("t--x", timeout_s=300)

    def deterministic_bytes(art):
        art = json.loads(canonical_json(art))
        art["run"].pop("wall_s", None)  # the only nondeterministic field
        return canonical_json(art)

    for cell in camp.expand():
        h = cell.spec_hash()
        a = deterministic_bytes(local_store.load_cell(h))
        b = deterministic_bytes(view.load_cell(h))
        assert a == b, cell.tag


# ====================================================== resilience (PR 9)
from repro import faults  # noqa: E402 — resilience-section imports
from repro.faults import FaultPlan, FaultRule  # noqa: E402
from repro.service import QueueSaturated  # noqa: E402


@pytest.fixture()
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def test_submit_backpressure_429_retry_after_and_cli_exit_3(
    tmp_path, capsys, _clean_faults
):
    """queue_high_water=0 saturates instantly: raw HTTP sees 429 with a
    Retry-After hint, the client raises a retryable ServiceError after
    its budget, and the CLI maps it to exit code 3 with a one-line
    diagnostic."""
    server, service = make_server(
        str(tmp_path / "svc"), workers=0, queue_high_water=0
    )
    threading.Thread(target=server.serve_forever, daemon=True).start()
    host, port = server.server_address[:2]
    url = f"http://{host}:{port}"
    spec = tmp_path / "spec.json"
    spec.write_text(tiny_campaign().dumps())
    try:
        with pytest.raises(QueueSaturated):
            service.submit(tiny_campaign().to_json(), tenant="direct")
        body = json.dumps(
            {"campaign": tiny_campaign().to_json(), "tenant": "raw"}
        ).encode()
        req = urllib.request.Request(
            url + "/campaigns", data=body,
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as raw:
            urllib.request.urlopen(req, timeout=30)
        assert raw.value.code == 429
        assert float(raw.value.headers["Retry-After"]) > 0

        client = ServiceClient(url, retries=1, backoff_base_s=0.01)
        with pytest.raises(ServiceError) as e:
            client.submit(tiny_campaign().to_json(), tenant="alice")
        assert e.value.code == 429 and e.value.retryable

        rc = cli_main(["campaign", "submit", str(spec), "--url", url,
                       "--no-wait", "--timeout", "5"])
        captured = capsys.readouterr()
        assert rc == 3
        assert captured.err.startswith("repro: error: ")
        assert captured.err.strip().count("\n") == 0
        assert "Traceback" not in captured.err + captured.out
    finally:
        server.shutdown()
        server.server_close()
        service.close()


def test_client_retries_through_injected_5xx_and_reset(served, _clean_faults):
    """One injected server 503 and one injected client-side connection
    reset are both absorbed by the retry loop — the call still
    succeeds."""
    faults.configure(FaultPlan(rules=[
        FaultRule("http.request", "error_5xx", max_fires=1),
        FaultRule("http.client", "reset", max_fires=1),
    ]))
    client = ServiceClient(
        served.base_url, retries=3, backoff_base_s=0.01, backoff_max_s=0.05
    )
    assert client.healthz() == {"ok": True}
    faults.configure(False)
    assert client.healthz() == {"ok": True}


def test_client_does_not_retry_permanent_4xx(served, _clean_faults):
    t0 = time.monotonic()
    client = ServiceClient(served.base_url, retries=3, backoff_base_s=0.5)
    with pytest.raises(ServiceError) as e:
        client.status("nope--missing")
    assert e.value.code == 404 and not e.value.retryable
    assert time.monotonic() - t0 < 0.5  # no backoff sleeps: failed fast


def test_events_stream_reconnects_after_injected_reset(served, _clean_faults):
    """A dropped event stream resumes from ?since=<cursor>: the client
    re-yields nothing twice and loses nothing — the reconnected event
    list is identical to a clean read."""
    camp = tiny_campaign()
    sub = served.submit(camp.to_json(), tenant="alice")
    served.wait(sub["submission_id"], timeout_s=300)
    clean = list(served.events(sub["submission_id"]))
    assert clean  # the stream has real content to lose
    client = ServiceClient(
        served.base_url, retries=3, backoff_base_s=0.01, backoff_max_s=0.05
    )
    faults.configure(FaultPlan(rules=[
        FaultRule("http.request", "reset", max_fires=2),
    ]))
    assert list(client.events(sub["submission_id"])) == clean


def test_unit_deadline_cancels_wedged_unit(tmp_path, monkeypatch):
    """A unit that heartbeats but never finishes (wedged decode) is
    cancelled at unit_deadline_s by worker replacement, counted in
    deadline_cancels, and announced with reason=unit_deadline."""
    monkeypatch.setenv(CELL_DELAY_ENV, "30.0")
    store = RunStore(str(tmp_path / "cells"))
    events = []
    cfg = SchedulerConfig(
        heartbeat_timeout_s=60.0, unit_deadline_s=1.0, max_retries=0,
    )
    sched = Scheduler(store, workers=1, config=cfg, on_event=events.append).start()
    try:
        sched.submit("c1", "alice", [tiny_campaign().expand()])
        _wait_for(lambda: any(e["type"] == "cell_started" for e in events))
        assert sched.wait("c1", timeout_s=120)
        state = sched.campaign_state("c1")
        m = sched.metrics()
    finally:
        sched.close()
    assert state["done"] and len(state["errors"]) == 1
    assert m["counters"]["deadline_cancels"] >= 1
    restarts = [e for e in events if e["type"] == "worker_restart"]
    assert any(e["reason"] == "unit_deadline" for e in restarts)
