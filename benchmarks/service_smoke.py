"""Campaign-service smoke (CI fast tier).

Boots the multi-tenant service on an ephemeral port, submits the 2-cell
``benchmarks/specs/service_smoke.json`` from two concurrent clients
(different tenants), and asserts the ISSUE-7 acceptance properties:

* every unique cell spec hash is decoded exactly once (the second tenant
  is pure dedup — checked against ``/metrics`` counters and the 0.5
  dedup hit rate);
* both served reports carry fronts bit-identical to a local
  ``CampaignRunner`` run of the same spec;
* the event streams replay per-cell progress and terminate.

Exits non-zero on any violation.

Run:  PYTHONPATH=src python -m benchmarks.service_smoke [--workers 2]
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import threading
import time

from repro.core import Campaign, CampaignRunner, RunStore
from repro.service import ServiceClient, make_server

# Host-only cells: pooled workers refuse device explorers (jax_nsga2).
DEFAULT_SPEC = os.path.join(os.path.dirname(__file__), "specs", "service_smoke.json")
TENANTS = ("alice", "bob")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--spec", default=DEFAULT_SPEC)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--root", default=None,
                    help="service store root (default: fresh temp dir)")
    args = ap.parse_args(argv)

    campaign = Campaign.load(args.spec)
    n_unique = len({c.spec_hash() for c in campaign.expand()})
    root = args.root or tempfile.mkdtemp(prefix="service-smoke-")
    server, service = make_server(root, port=0, workers=args.workers)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    host, port = server.server_address[:2]
    client = ServiceClient(f"http://{host}:{port}")
    print(f"service on http://{host}:{port} ({args.workers} workers, store {root})")

    statuses = {}
    errors = []

    def submit(tenant: str) -> None:
        try:
            sub = client.submit(campaign.to_json(), tenant=tenant)
            n_events = sum(1 for _ in client.events(sub["submission_id"]))
            statuses[tenant] = client.wait(sub["submission_id"], timeout_s=600)
            statuses[tenant]["_streamed_events"] = n_events
        except Exception as e:  # noqa: BLE001 — surface in the summary
            errors.append(f"{tenant}: {type(e).__name__}: {e}")

    t0 = time.monotonic()
    threads = [threading.Thread(target=submit, args=(t,)) for t in TENANTS]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0

    failures = list(errors)
    try:
        metrics = client.metrics()
    finally:
        server.shutdown()
        server.server_close()
        service.close()

    counters = metrics["counters"]
    print(
        f"{len(TENANTS)} tenants x {n_unique} cells in {wall:.1f}s: "
        f"executed={counters['cells_executed']} "
        f"deduped={counters['cells_deduped']} "
        f"dedup_hit_rate={metrics['dedup_hit_rate']:.2f}"
    )
    if counters["cells_executed"] != n_unique:
        failures.append(
            f"expected exactly one decode per unique hash ({n_unique}), "
            f"got cells_executed={counters['cells_executed']}"
        )
    if counters["cells_deduped"] != n_unique * (len(TENANTS) - 1):
        failures.append(
            f"expected {n_unique * (len(TENANTS) - 1)} dedup hits, "
            f"got {counters['cells_deduped']}"
        )

    local = CampaignRunner(campaign, store=RunStore(None)).run()
    for tenant in TENANTS:
        status = statuses.get(tenant)
        if status is None:
            continue
        report = status["report"]
        if not status["done"] or report["missing"]:
            failures.append(f"{tenant}: incomplete ({report['missing']})")
            continue
        for tag in local.cells:
            got = [tuple(p) for p in report["cells"][tag]["front"]]
            if got != local.front(tag):
                failures.append(f"{tenant}: front diverged from local run ({tag})")
        if status["_streamed_events"] < n_unique:
            failures.append(
                f"{tenant}: event stream too short "
                f"({status['_streamed_events']} events)"
            )
        print(f"  {tenant}: report identical to local CampaignRunner, "
              f"{status['_streamed_events']} events streamed")

    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    print("service_smoke:", "FAIL" if failures else "OK")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
