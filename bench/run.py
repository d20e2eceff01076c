#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  Every name resolves to data under ``bench/``:
the cell to its entry in ``BENCHMARK.json``, its configuration to
``configs/<name>.json``, its traffic to ``mixes/<name>.json`` and each
per-layer metric to ``layer_metrics/<name>.py``.  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window and from the
program's spans.

The last line of standard output is one JSON object; the numbers that
decided ``correct`` come last there, under ``checks``, and as the last
lines of standard error.  Without a TPU, or with fewer chips than the cell
asks for, the run exits 2 and prints no result.

    JAX_PLATFORMS=cpu python3 bench/run.py --workload <cell> --seed 1 \\
        --seconds 5 --trace 1 --cpu-rehearsal [--tiny]

runs the same path on the CPU (``--tiny``: population 8, 4 offspring),
prints what it measured to standard error, and exits 1 without a result:
it is not a chip run.  ``--control`` puts the reference, computed at the
next lower precision, in the program's place for the comparison; its run
must come out not correct.
"""
from __future__ import annotations

import time

T_START = time.perf_counter_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TINY = dict(population=8, offspring=4)


def _print_checks(numbers) -> None:
    for name, n in numbers.items():
        print(f"check {name} = {n['value']!r} (limit {n['limit']!r}, "
              f"{n['checked']} checked)", file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    if args.tiny and not args.cpu_rehearsal:
        ap.error("--tiny is for the CPU rehearsal only")

    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    if not any(w["name"] == args.workload for w in spec["workloads"]):
        ap.error(f"no workload {args.workload!r} in BENCHMARK.json")
    # JAX's persistent compile cache lives at a fixed path in the checkout.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    import harness

    try:
        out = harness.run_cell(
            spec, args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START, require_tpu=not args.cpu_rehearsal,
            search=TINY if args.tiny else None, control=args.control,
        )
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if args.cpu_rehearsal:
        print(json.dumps(out), file=sys.stderr)
        _print_checks(out["checks"])
        print("CPU rehearsal: not a chip run, no result", file=sys.stderr)
        return 1
    print(f"timing {json.dumps(out.pop('timing'))}", file=sys.stderr)
    _print_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
