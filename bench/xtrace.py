"""Host annotations and device ops of one profiler trace, on the profile's
own clock.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote: the
program's spans as the profiler recorded them (``TraceAnnotation`` events
on the host threads, ``explorer.generation`` numbered by ``step_num``)
and every device op with the part of the step it belongs to.  An op's
part is the innermost of ``PARTS`` in its name stack, taken from the
first source that has one:

1. the op event's own stats (``tf_op``, ``long_name``);
2. the HLO module the trace stores for the program (``hlo_op`` →
   ``metadata.op_name``; an instruction without one takes the part of
   the instructions it calls, or else of the instruction that calls it).
   A CPU op event names its op and program in its stats; a TPU op event
   carries neither, so its op comes from its name, the op's HLO text, and
   its program from the ``XLA Modules`` event running at its start.

Nothing here reads the JSON-lines spans' clock or ``Window.trace_t0``,
except ``annotation_offsets``, which measures how far that clock is from
the profile's.
"""
from __future__ import annotations

import bisect
import glob
import itertools
import os
import re
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

from tracing import CPU_SKIP, DEVICE_OP_LINES, union

PARTS = ("rank", "vary", "decode", "simulate")
NAME_STATS = ("tf_op", "long_name")        # event stats that may hold a name stack
HLO_PROTO_STAT = "Hlo Proto"
SPAN_NAME = re.compile(r"[a-z_]+(\.[a-z_]+)+$")  # the program's span names
HLO_TEXT = re.compile(r"%?([^\s=%]+) = ")          # a TPU op event's name: its HLO text
MODULE_RUN = re.compile(r"(.+)\((\d+)\)$")         # an XLA Modules event: jit_step(12)
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


@dataclass(slots=True)
class Annotation:
    name: str
    start: int
    end: int
    step: Optional[int] = None


@dataclass(slots=True)
class Op:
    start: int
    end: int
    part: Optional[str]


@dataclass
class Profile:
    annotations: List[Annotation]
    ops: List[Op]                                   # sorted by start
    part_source: Optional[str] = None               # which source named the parts
    _starts: List[int] = field(default_factory=list, repr=False)
    _max_end: List[int] = field(default_factory=list, repr=False)

    def __post_init__(self) -> None:
        self.ops.sort(key=lambda o: o.start)
        self._starts = [o.start for o in self.ops]
        self._max_end = list(itertools.accumulate((o.end for o in self.ops), max))

    def named(self, name: str) -> List[Annotation]:
        return [a for a in self.annotations if a.name == name]

    def _first_reaching(self, lo: int) -> int:
        """Index of the first op that may end after ``lo``."""
        i = bisect.bisect_left(self._starts, lo)
        while i > 0 and self._max_end[i - 1] > lo:
            i -= 1
        return i

    def ops_between(self, lo: int, hi: int) -> List[Op]:
        """Ops whose middle lies in ``[lo, hi)``."""
        out = []
        for op in self.ops[self._first_reaching(lo):]:
            if op.start >= hi:
                break
            if lo <= (op.start + op.end) // 2 < hi:
                out.append(op)
        return out

    def overlaps_op(self, lo: int, hi: int) -> bool:
        """Whether any op runs inside ``(lo, hi)``: one started before
        ``lo`` ends after it, or one starts before ``hi``."""
        i = bisect.bisect_left(self._starts, lo)
        return ((i > 0 and self._max_end[i - 1] > lo)
                or (i < len(self.ops) and self._starts[i] < hi))


# ------------------------------------------------------------------ parts
def part_of(name_stack: str) -> Optional[str]:
    """Innermost part named in an op's name stack (``jit(step)/vmap(decode)/add``
    → ``decode``)."""
    found = None
    for word in _WORD.findall(name_stack or ""):
        if word in PARTS:
            found = word
    return found


# A minimal reader of the protobuf wire format: the trace stores the HLO
# as serialized protos, and no proto module for them is part of JAX.
def _varint(buf, i: int) -> Tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """``(field number, value)``: an int for varints, a memoryview for
    length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield num, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")


def _str(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _packed(view) -> List[int]:
    out, i = [], 0
    while i < len(view):
        v, i = _varint(view, i)
        out.append(v)
    return out


def hlo_parts(hlo_proto) -> Dict[str, Optional[str]]:
    """Instruction name → part, from one serialized ``HloProto``."""
    comps: Dict[int, List[Tuple[str, Optional[str], List[int]]]] = {}
    for num, module in _fields(hlo_proto):
        if num != 1:                                    # HloProto.hlo_module
            continue
        for num2, comp in _fields(module):
            if num2 != 3:                               # HloModuleProto.computations
                continue
            cid, instrs = None, []
            for num3, val in _fields(comp):
                if num3 == 5:                           # HloComputationProto.id
                    cid = val
                elif num3 == 2:                         # .instructions
                    name, stack, called = "", "", []
                    for num4, v in _fields(val):
                        if num4 == 1:
                            name = _str(v)
                        elif num4 == 7:                 # OpMetadata
                            for num5, w in _fields(v):
                                if num5 == 2:           # op_name
                                    stack = _str(w)
                        elif num4 == 38:                # called_computation_ids
                            called += _packed(v) if isinstance(v, memoryview) else [v]
                    instrs.append((name, part_of(stack), called))
            comps[cid] = instrs
    # A computation's own part: the most common among its instructions.
    own = {}
    for cid, instrs in comps.items():
        votes = Counter(p for _, p, _ in instrs if p)
        own[cid] = votes.most_common(1)[0][0] if votes else None
    # Parts flow down from a caller to the computations it calls.
    inherited: Dict[int, Optional[str]] = {}

    def push(cid: int, part: Optional[str], seen=()) -> None:
        if cid in seen or cid not in comps or inherited.get(cid) is not None:
            return
        inherited[cid] = part
        for _, p, called in comps[cid]:
            for c in called:
                push(c, p or part, seen + (cid,))

    called_any = {c for instrs in comps.values() for _, _, cs in instrs for c in cs}
    for cid in comps:
        if cid not in called_any:
            push(cid, None)
    out: Dict[str, Optional[str]] = {}
    for cid, instrs in comps.items():
        for name, part, called in instrs:
            if part is None:
                votes = Counter(own.get(c) for c in called if own.get(c))
                part = votes.most_common(1)[0][0] if votes else inherited.get(cid)
            out[name] = part
    return out


def stored_hlo(path: str) -> Dict[str, bytes]:
    """Serialized ``HloProto`` per program (``<module>(<program id>)``)
    from the trace's ``/host:metadata`` plane."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[str, bytes] = {}
    for num, plane in _fields(buf):
        if num != 1:                                    # XSpace.planes
            continue
        name, stat_names, metas = None, {}, []
        for num2, val in _fields(plane):
            if num2 == 2:                               # XPlane.name
                name = _str(val)
                if name != "/host:metadata":
                    break
            elif num2 == 4:                             # event_metadata map entry
                metas.append(val)
            elif num2 == 5:                             # stat_metadata map entry
                for num3, sm in _fields(val):
                    if num3 == 2:
                        sid, sname = None, ""
                        for num4, v in _fields(sm):
                            if num4 == 1:
                                sid = v
                            elif num4 == 2:
                                sname = _str(v)
                        stat_names[sid] = sname
        if name != "/host:metadata":
            continue
        for entry in metas:
            for num3, em in _fields(entry):
                if num3 != 2:
                    continue
                ename, protos = "", []
                for num4, v in _fields(em):
                    if num4 == 2:                       # XEventMetadata.name
                        ename = _str(v)
                    elif num4 == 5:                     # .stats
                        sid, data = None, None
                        for num5, w in _fields(v):
                            if num5 == 1:
                                sid = w
                            elif num5 == 6:             # bytes_value
                                data = bytes(w)
                        if data is not None:
                            protos.append((sid, data))
                for sid, data in protos:
                    if stat_names.get(sid) == HLO_PROTO_STAT:
                        out[ename] = data
    return out


# ------------------------------------------------------------------- load
def trace_file(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> Profile:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    tpu = {p.name for p in planes if p.name == "/device:TPU:0" or p.name.startswith("/device:TPU:0 ")}
    annotations: List[Annotation] = []
    ops: List[Op] = []
    namer = _OpNamer(path)
    for plane in planes:
        if plane.name == "/host:CPU":
            # On the CPU the ops run on the client's worker threads and,
            # some, on the calling thread itself: an op is an event with
            # an ``hlo_op``, whatever its line.
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if SPAN_NAME.match(name):
                        step = dict(ev.stats).get("step_num")
                        annotations.append(Annotation(
                            name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                            None if step is None else int(step)))
                    elif not tpu and ev.duration_ns > 0 and not name.startswith(CPU_SKIP + ("end: ", "$")):
                        op = namer.op(ev)
                        if op is not None:
                            ops.append(op)
        elif plane.name in tpu:
            ops.extend(device_ops({ln.name: ln for ln in plane.lines}, namer))
    sources = namer.sources
    return Profile(annotations, ops, max(sources, key=sources.get) if sources else None)


def device_ops(lines: Dict[str, Any], namer: "_OpNamer") -> List[Op]:
    """The ops of a TPU plane's op line.  Their events carry no ``hlo_op``
    or module stat: the name is the op's HLO text (``%fusion.16 = ...``),
    and the program is the ``XLA Modules`` event running at the op's start
    (``jit_step(12)``)."""
    runs = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns), ev.name)
                  for ev in getattr(lines.get("XLA Modules"), "events", ()))
    run_starts = [r[0] for r in runs]
    out = []
    for ln in [lines[n] for n in DEVICE_OP_LINES if n in lines][:1]:
        for ev in ln.events:
            if ev.duration_ns > 0:
                start = int(ev.start_ns)
                i = bisect.bisect_right(run_starts, start) - 1
                module = runs[i][2] if i >= 0 and start < runs[i][1] else None
                out.append(namer.op(ev, device=True, module=module))
    return out


class _OpNamer:
    """Each op's part, remembered per (program, op), and how many ops each
    source named."""

    def __init__(self, path: str) -> None:
        self.path = path
        self.sources: Counter = Counter()
        self._modules: Optional[Dict[str, Dict[str, Optional[str]]]] = None
        self._memo: Dict[Tuple, Tuple[Optional[str], Optional[str]]] = {}

    def op(self, ev, device: bool = False, module: Optional[str] = None) -> Optional[Op]:
        """The event as an op, or None for a host event that is no op."""
        stats = {}
        for key, value in ev.stats:
            if key in NAME_STATS or key in ("hlo_op", "hlo_module", "program_id"):
                stats[key] = value
        if device:
            named = HLO_TEXT.match(ev.name)
            if "hlo_op" not in stats and named:
                stats["hlo_op"] = named.group(1)
            if "hlo_module" not in stats and module:
                run = MODULE_RUN.match(module)
                stats["hlo_module"], stats["program_id"] = (
                    (run.group(1), int(run.group(2))) if run else (module, None))
        elif "hlo_op" not in stats:
            return None
        key = tuple(sorted(stats.items()))
        hit = self._memo.get(key)
        if hit is None:
            hit = self._memo[key] = self._part(stats)
        part, source = hit
        if source:
            self.sources[source] += 1
        start = int(ev.start_ns)
        return Op(start, start + int(ev.duration_ns), part)

    def _part(self, stats) -> Tuple[Optional[str], Optional[str]]:
        for key in NAME_STATS:
            part = part_of(str(stats.get(key, "")))
            if part:
                return part, "event stat " + key
        if "hlo_op" in stats:
            if self._modules is None:
                self._modules = {k: hlo_parts(v) for k, v in stored_hlo(self.path).items()}
            part = _stored_part(self._modules, stats.get("hlo_module"),
                                stats.get("program_id"), str(stats["hlo_op"]))
            if part:
                return part, "stored HLO"
        return None, None


def _stored_part(modules, module: Optional[str], program_id, op: str) -> Optional[str]:
    """The op's part in the stored module of its program; a program loaded
    from the persistent cache runs under another id than the one its HLO
    is stored under, so then any stored module of that name that has the op.
    With the program unknown, the part every stored module gives the op,
    if they agree."""
    table = modules.get(f"{module}({program_id})")
    if table is not None:
        return table.get(op)
    found = {table[op] for key, table in modules.items()
             if op in table and module in (None, key.rsplit("(", 1)[0])}
    return found.pop() if len(found) == 1 else None


def profile(ctx: Dict[str, Any]) -> Optional[Profile]:
    """The profile of a traced run's window, read once per run."""
    if "profile" not in ctx:
        window = ctx.get("window")
        path = trace_file(getattr(window, "trace_dir", None) or "")
        ctx["profile"] = load(path) if path else None
    return ctx["profile"]


# ---------------------------------------------------------------- metrics
def covered_generations(prof: Profile) -> List[Annotation]:
    """Generations the profile holds whole: device ops inside every
    ``evo.execute`` annotation within the generation and, where a later
    ``evo.execute`` holds none (the profile's device buffer ran out), ops
    up to the end of the generation's last ``evo.execute``: a generation
    whose device work the buffer cut off partway is left out."""
    execs = sorted(prof.named("evo.execute"), key=lambda a: a.start)
    starts = [a.start for a in execs]
    held = [prof.overlaps_op(a.start, a.end) for a in execs]
    cut = not all(held)
    last_end = prof._max_end[-1] if prof.ops else None
    out = []
    for g in prof.named("explorer.generation"):
        lo, hi = bisect.bisect_left(starts, g.start), bisect.bisect_right(starts, g.end)
        inside = [i for i in range(lo, hi) if execs[i].end <= g.end]
        if (inside and all(held[i] for i in inside)
                and not (cut and execs[inside[-1]].end > last_end)):
            out.append(g)
    return out


def part_ns_per_generation(prof: Profile) -> Optional[Dict[str, float]]:
    """Per covered generation: each part's busy time (union of its ops'
    intervals), ``busy`` (union of all ops) and ``unattributed``, in ns.
    None when no op carries a part (a program without named parts)."""
    gens = covered_generations(prof)
    if not gens or not any(op.part for op in prof.ops):
        return None
    total: Counter = Counter()
    for g in gens:
        ops = prof.ops_between(g.start, g.end)
        for key in PARTS + (None,):
            total[key or "unattributed"] += _busy([o for o in ops if o.part == key])
        total["busy"] += _busy(ops)
    return {k: total[k] / len(gens) for k in PARTS + ("busy", "unattributed")}


def _busy(ops: List[Op]) -> int:
    return sum(e - s for s, e in union([(o.start, o.end) for o in ops]))


def execute_coverage(prof: Profile, spans, t_open: int, t_close: int) -> Optional[float]:
    """Share of the ``evo.execute`` spans recorded in the window that the
    profile holds as annotations with at least one device op inside.
    None when the profile holds no annotation of the program's at all."""
    if not prof.named("explorer.generation"):
        return None
    recorded = [s for s in spans if s["name"] == "evo.execute"
                and t_open <= s["ts"] and s["ts"] + s["dur"] <= t_close]
    if not recorded:
        return None
    held = [a for a in prof.named("evo.execute") if prof.overlaps_op(a.start, a.end)]
    return len(held) / len(recorded)


def annotation_offsets(prof: Profile, spans, trace_t0: int) -> List[int]:
    """For each ``explorer.generation`` span found in the profile (matched
    by generation number): its annotation's start less its JSON-lines
    start mapped through ``trace_t0`` (ns)."""
    starts = {a.step: a.start for a in prof.named("explorer.generation") if a.step is not None}
    return [starts[s["attrs"]["gen"]] - (s["ts"] - trace_t0) for s in spans
            if s["name"] == "explorer.generation" and s.get("attrs", {}).get("gen") in starts]


def part_ms(ctx: Dict[str, Any], part: str) -> Optional[float]:
    """A part's device time per covered generation (ms), or None."""
    if "part_ns" not in ctx:
        prof = profile(ctx)
        ctx["part_ns"] = part_ns_per_generation(prof) if prof else None
    per = ctx["part_ns"]
    return None if per is None else per[part] / 1e6
