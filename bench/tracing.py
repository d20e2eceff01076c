"""Reduction of a profiler trace and of the program's spans to numbers.

``reduce`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote for the
measured window and returns the device's op intervals on the window's own
clock (nanoseconds of ``time.perf_counter_ns``): busy time as the union of
the intervals, the op time by name, and the idle gaps.  ``breakdown``
names the longest gaps by the innermost program span open during each.

    python3 bench/tracing.py --self-test

records a small trace on the CPU and checks the reduction against what
the test ran.
"""
from __future__ import annotations

import glob
import os
from typing import Any, Dict, Iterator, List, Tuple

# Lines of a device plane that hold one event per executed operation, in
# order of preference: the first that a trace has is read.
DEVICE_OP_LINES = ("XLA Ops", "XLA Modules")
CPU_OP_LINE_PREFIX = "tf_XLA"       # the CPU client's worker threads
CPU_SKIP = ("ThreadpoolListener", "ThunkExecutor")   # markers, not ops


def _device_events(planes, platform: str) -> List[Tuple[str, int, int]]:
    """``(name, start_ns, duration_ns)`` of every device operation, start
    relative to the trace's own zero."""
    out = []
    for plane in planes:
        if platform == "cpu":
            if plane.name != "/host:CPU":
                continue
            lines = [ln for ln in plane.lines if ln.name.startswith(CPU_OP_LINE_PREFIX)]
        else:
            name = f"/device:{platform.upper()}:0"
            if plane.name != name and not plane.name.startswith(name + " "):
                continue
            by_name = {ln.name: ln for ln in plane.lines}
            lines = [by_name[n] for n in DEVICE_OP_LINES if n in by_name][:1]
        for line in lines:
            for ev in line.events:
                if ev.duration_ns > 0 and not ev.name.startswith(CPU_SKIP):
                    out.append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def reduce(trace_dir: str, t0_ns: int, t1_ns: int, platform: str) -> Dict[str, Any]:
    """Device busy and idle over the traced window ``[t0_ns, t1_ns)`` of
    the window clock; ``t0_ns`` is its reading when the profiler started,
    and trace times are offsets from that start."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    planes = list(pd.planes)
    lo, hi = 0, t1_ns - t0_ns
    events = [(name, max(s, lo), min(s + d, hi) - max(s, lo))
              for name, s, d in _device_events(planes, platform)
              if s < hi and s + d > lo]
    busy = union([(s, s + d) for _, s, d in events])
    by_op: Dict[str, int] = {}
    for name, _, d in events:
        by_op[name] = by_op.get(name, 0) + d
    gaps = []
    prev = lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev + t0_ns, s + t0_ns))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev + t0_ns, hi + t0_ns))
    busy_ns = sum(e - s for s, e in busy)
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9,
        "ops": len(events),
        "op_ns": by_op,
        "gaps": gaps,                        # (start, end) on the window clock
    }


def obs_spans(obs_dir: str) -> Iterator[Dict[str, Any]]:
    """The program's span records (``repro.obs`` sink files), with
    ``ts``/``dur`` in ``time.perf_counter_ns`` nanoseconds."""
    import json

    for name in sorted(os.listdir(obs_dir)) if os.path.isdir(obs_dir) else ():
        if not name.endswith(".jsonl"):
            continue
        with open(os.path.join(obs_dir, name)) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("t") == "span":
                    yield rec


def innermost(spans: List[Dict[str, Any]], t: int) -> str:
    """Name of the shortest program span open at ``t``, or ``"no span"``."""
    best, best_dur = "no span", None
    for sp in spans:
        if sp["ts"] <= t < sp["ts"] + sp["dur"] and (best_dur is None or sp["dur"] < best_dur):
            best, best_dur = sp["name"], sp["dur"]
    return best


def breakdown(dev: Dict[str, Any], spans: List[Dict[str, Any]], top: int = 10):
    """Top device ops by time and the longest idle gaps by what the host
    was doing in them (the innermost span open at the gap's middle)."""
    ops = sorted(dev["op_ns"].items(), key=lambda kv: -kv[1])[:top]
    by_host: Dict[str, int] = {}
    for s, e in dev["gaps"]:
        name = innermost(spans, (s + e) // 2)
        by_host[name] = max(by_host.get(name, 0), e - s)
    gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[name, ns / 1e9] for name, ns in ops],
        "idle_gaps": [[name, ns / 1e9] for name, ns in gaps],
    }


def self_test() -> int:
    """Trace a known loop on the CPU and check what the reduction reads."""
    import tempfile
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x) + 1.0)
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        t0 = time.perf_counter_ns()
        jax.profiler.start_trace(d)
        for _ in range(8):
            f(x).block_until_ready()
            time.sleep(0.02)
        jax.profiler.stop_trace()
        t1 = time.perf_counter_ns()
        dev = reduce(d, t0, t1, "cpu")
    problems = []
    if not dev["ops"]:
        problems.append("no operation found")
    if not 0 < dev["busy_s"] <= dev["window_s"]:
        problems.append(f"busy {dev['busy_s']} outside (0, window {dev['window_s']}]")
    if dev["window_s"] > (t1 - t0) / 1e9:
        problems.append("traced window longer than the wall time around it")
    if not any(name.startswith("dot") for name in dev["op_ns"]):
        problems.append(f"no dot among {sorted(dev['op_ns'])[:8]}")
    # Eight sleeps of 20 ms lie between the calls: the gaps hold them.
    idle = sum(e - s for s, e in dev["gaps"]) / 1e9
    if idle < 8 * 0.02 * 0.9:
        problems.append(f"idle {idle} s shorter than the sleeps")
    spans = [{"name": "sleep", "ts": dev["gaps"][-2][0], "dur": 10 ** 9}]
    if breakdown(dev, spans)["idle_gaps"][0][0] != "sleep":
        problems.append("gap not attributed to the span open in it")
    print(f"self-test: {dev['ops']} ops, busy {dev['busy_s']} s of {dev['window_s']} s, "
          f"idle {idle} s: {'FAIL ' + '; '.join(problems) if problems else 'ok'}")
    return 1 if problems else 0


if __name__ == "__main__":
    import sys

    if sys.argv[1:] != ["--self-test"]:
        sys.exit("usage: python3 bench/tracing.py --self-test")
    sys.exit(self_test())
