"""Plain model of a mapped dataflow application: MRB substitution, the
tasks of one firing, and its self-timed execution.

Written from the paper's model (arXiv 2311.17473: §III Algorithm 1 for
the substitution, §IV and Eq. 11 for the actor window and the
communication times) and from the self-timed firing rule the repository
documents for its simulators, directly on the configuration's JSON form:

    graph = {"actors":   {name: {"exec_times": {ctype: τ}, "multicast": bool}},
             "channels": {name: {"src", "dsts", "delay", "capacity",
                                 "token_bytes"}}}           # insertion order
    arch  = {"cores": {name: {"tile", "ctype"}},
             "memories": {name: {"kind", "capacity", "tile", "owner_core"}},
             "interconnects": {name: {"bandwidth", ...}}, "noc", "global_memory"}

Channel state is a token count per distinct reader: a write adds a token
to every reader's count, a read takes one from its own, and a channel has
a free place while no reader's count has reached its capacity.

Firing rule, applied in synchronous rounds at one instant until a round
changes nothing, then time jumps to the next task completion:

1. completions: every running task that is due ends; reads take effect
   before every other task;
2. window starts: an actor whose core is free, with a token on every input
   and a free place on every output, opens a firing window; per core the
   first actor in arbitration order wins, and the core is held until the
   window's last task ends;
3. task starts: the current task of each open window may start when its
   token or place is there and every interconnect it crosses is free; a
   timed task yields (to a later round at the same instant) to any earlier
   candidate in arbitration order that is timed and crosses one of its
   interconnects.  Zero-length tasks take effect at once, reads first;
   timed tasks hold their interconnects until they end.

A round in which every candidate started a timed task cannot enable
anything more at that instant, and closes the instant.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

READ, EXEC, WRITE = "read", "exec", "write"

# A task: (kind, channel or None, duration, interconnects crossed)
Task = Tuple[str, Optional[str], int, frozenset]


# -------------------------------------------------------------- the graph
def multicast(graph: dict) -> List[str]:
    """Actors flagged multi-cast, in the graph's own order."""
    return [a for a, spec in graph["actors"].items() if spec.get("multicast")]


def substitute(graph: dict, xi: Dict[str, int]) -> dict:
    """Algorithm 1: every multi-cast actor with ξ = 1 and its input and
    output channels become one multi-reader buffer, written by the input's
    producer and read by every reader of the outputs.  Its capacity is the
    input's plus one output's, its initial tokens the input's, its token
    size the input's; it is appended after the remaining channels."""
    actors = {a: dict(spec) for a, spec in graph["actors"].items()}
    channels = {c: dict(spec, dsts=list(spec["dsts"])) for c, spec in graph["channels"].items()}
    for am in multicast(graph):
        if not xi.get(am, 0):
            continue
        ins = [c for c, ch in channels.items() if am in ch["dsts"]]
        outs = [c for c, ch in channels.items() if ch["src"] == am]
        if len(ins) != 1 or not outs:
            raise ValueError(f"{am} is not a multi-cast actor")
        cin = channels[ins[0]]
        readers = [r for c in outs for r in channels[c]["dsts"]]
        merged = {
            "src": cin["src"], "dsts": readers, "delay": cin["delay"],
            "capacity": cin["capacity"] + channels[outs[0]]["capacity"],
            "token_bytes": cin["token_bytes"], "is_mrb": True,
            "members": sorted(ins + outs),
        }
        del actors[am]
        for c in ins + outs:
            del channels[c]
        channels["mrb{" + ",".join(merged["members"]) + "}"] = merged
    return {"name": graph.get("name", "app"), "actors": actors, "channels": channels}


def pipelined(graph: dict) -> dict:
    """§VI: one initial token on every channel that has none."""
    channels = {c: dict(ch, delay=max(ch["delay"], 1)) for c, ch in graph["channels"].items()}
    return dict(graph, channels=channels)


def readers(ch: dict) -> List[str]:
    """Distinct readers of a channel, first listing first."""
    return list(dict.fromkeys(ch["dsts"]))


def arbitration_order(graph: dict) -> List[str]:
    """Topological order over the channels without initial tokens,
    choosing the least name among the actors that are ready."""
    preds: Dict[str, set] = {a: set() for a in graph["actors"]}
    for ch in graph["channels"].values():
        if ch["delay"] == 0:
            for r in ch["dsts"]:
                preds[r].add(ch["src"])
    order: List[str] = []
    placed: set = set()
    while len(order) < len(preds):
        ready = [a for a in preds if a not in placed and preds[a] <= placed]
        if not ready:
            raise ValueError("a cycle without initial tokens")
        a = min(ready)
        order.append(a)
        placed.add(a)
    return order


# ------------------------------------------------------------ the platform
def crossed(arch: dict, core: str, memory: str) -> List[str]:
    """Interconnects an access from ``core`` to ``memory`` crosses: none
    for the core's own memory, the tile's crossbar inside the tile, the
    crossbar and the NoC to the global memory, and both tiles' crossbars
    and the NoC to another tile's memory."""
    tile = arch["cores"][core]["tile"]
    mem = arch["memories"][memory]
    xbar = f"h_{tile}"
    if mem["kind"] == "core_local" and mem.get("owner_core") == core:
        return []
    if mem["kind"] == "global":
        return [xbar, arch["noc"]]
    if mem.get("tile") == tile:
        return [xbar]
    return [xbar, arch["noc"], f"h_{mem['tile']}"]


def comm_time(arch: dict, nbytes: int, core: str, memory: str) -> int:
    """Eq. 11: token bytes over the narrowest interconnect crossed, rounded
    up, at least 1; 0 when nothing is crossed."""
    links = crossed(arch, core, memory)
    if not links:
        return 0
    narrowest = min(arch["interconnects"][h]["bandwidth"] for h in links)
    return max(1, math.ceil(nbytes / narrowest))


def firing(graph: dict, arch: dict, core_of: Dict[str, str],
           mem_of: Dict[str, str]) -> Dict[str, List[Task]]:
    """The tasks of one firing window per actor: a read of each input
    channel (in channel order), the execution on the bound core's type,
    a write of each output channel (in channel order)."""
    out: Dict[str, List[Task]] = {}
    for a, spec in graph["actors"].items():
        p = core_of[a]
        tasks: List[Task] = []
        for c, ch in graph["channels"].items():
            if a in ch["dsts"]:
                tasks.append((READ, c, comm_time(arch, ch["token_bytes"], p, mem_of[c]),
                               frozenset(crossed(arch, p, mem_of[c]))))
        tasks.append((EXEC, None, spec["exec_times"][arch["cores"][p]["ctype"]], frozenset()))
        for c, ch in graph["channels"].items():
            if ch["src"] == a:
                tasks.append((WRITE, c, comm_time(arch, ch["token_bytes"], p, mem_of[c]),
                              frozenset(crossed(arch, p, mem_of[c]))))
        out[a] = tasks
    return out


# ------------------------------------------------------------ execution
def execute(graph: dict, arch: dict, core_of: Dict[str, str], mem_of: Dict[str, str],
            capacity: Dict[str, int], firings: int) -> Tuple[Dict[str, List[int]], bool]:
    """Self-timed execution until every actor has fired ``firings`` times:
    (start time of every firing per actor, deadlocked)."""
    order = arbitration_order(graph)
    tasks = firing(graph, arch, core_of, mem_of)
    reads = {a: [c for kind, c, _, _ in tasks[a] if kind == READ] for a in order}
    writes = {a: [c for kind, c, _, _ in tasks[a] if kind == WRITE] for a in order}
    tokens: Dict[str, Dict[str, int]] = {}
    room: Dict[str, int] = {}           # places free for the writer
    for c, ch in graph["channels"].items():
        if ch["delay"] > capacity[c]:
            raise ValueError(f"{c}: more initial tokens than places")
        tokens[c] = {r: ch["delay"] for r in readers(ch)}
        room[c] = capacity[c] - ch["delay"]

    holder: Dict[str, Optional[str]] = {core_of[a]: None for a in order}
    link_free = {h: 0 for h in arch["interconnects"]}
    in_window = {a: False for a in order}
    running = {a: False for a in order}
    until = {a: 0 for a in order}
    pos = {a: 0 for a in order}
    done = {a: 0 for a in order}
    starts: Dict[str, List[int]] = {a: [] for a in order}

    def finish(a: str, task: Task) -> None:
        kind, c = task[0], task[1]
        if kind == READ:
            tokens[c][a] -= 1
            room[c] = capacity[c] - max(tokens[c].values())
        elif kind == WRITE:
            for r in tokens[c]:
                tokens[c][r] += 1
            room[c] -= 1
        pos[a] += 1
        if pos[a] == len(tasks[a]):
            holder[core_of[a]] = None
            in_window[a] = False
            done[a] += 1

    t = 0
    while True:
        while True:
            due = [(a, tasks[a][pos[a]]) for a in order if running[a] and until[a] <= t]
            for a, _ in due:
                running[a] = False
            for a, task in due:
                if task[0] == READ:
                    finish(a, task)
            for a, task in due:
                if task[0] != READ:
                    finish(a, task)
            changed = bool(due)

            opened: Dict[str, str] = {}
            for a in order:
                p = core_of[a]
                if in_window[a] or done[a] >= firings or holder[p] is not None or p in opened:
                    continue
                if all(tokens[c][a] > 0 for c in reads[a]) and all(room[c] > 0 for c in writes[a]):
                    opened[p] = a
            for p, a in opened.items():
                holder[p] = a
                in_window[a] = True
                pos[a] = 0
                starts[a].append(t)
                changed = True

            cands = []
            for a in order:
                if not in_window[a] or running[a]:
                    continue
                task = tasks[a][pos[a]]
                kind, c, _, links = task
                if kind == READ and tokens[c][a] < 1:
                    continue
                if kind == WRITE and room[c] < 1:
                    continue
                if any(link_free[h] > t for h in links):
                    continue
                cands.append((a, task))
            winners = [
                (a, task) for i, (a, task) in enumerate(cands)
                if not any(other[2] > 0 and other[3] & task[3] for _, other in cands[:i])
            ]
            for a, task in winners:
                if task[2] == 0 and task[0] == READ:
                    finish(a, task)
                    changed = True
            for a, task in winners:
                if task[2] == 0 and task[0] != READ:
                    finish(a, task)
                    changed = True
            for a, task in winners:
                if task[2] > 0:
                    for h in task[3]:
                        link_free[h] = t + task[2]
                    running[a] = True
                    until[a] = t + task[2]
                    changed = True
            if not changed:
                break
            if len(winners) == len(cands) and all(task[2] > 0 for _, task in winners):
                break
        if all(done[a] >= firings for a in order):
            return starts, False
        pending = [until[a] for a in order if running[a]]
        if not pending:
            return starts, True
        t = min(pending)


# --------------------------------------------------------------- period
def steady_period(starts: Dict[str, Sequence[int]], max_multiplicity: int = 16,
                  checks: int = 3) -> Optional[float]:
    """The slowest actor's steady rate, or None while some actor's firings
    are not yet periodic.  The last quarter of each actor's firings (at
    least two) is left out, since the run ends draining.  An actor is
    periodic with multiplicity R when its last ``checks`` spans of R
    firings all take the same time D; its rate is D / R, with the least R."""
    worst: Optional[float] = None
    for ts in starts.values():
        ts = list(ts)[: max(0, len(ts) - max(2, len(ts) // 4))]
        rate = None
        for r in range(1, max_multiplicity + 1):
            if len(ts) < r * checks + 1:
                break
            spans = {ts[len(ts) - 1 - j * r] - ts[len(ts) - 1 - (j + 1) * r] for j in range(checks)}
            if len(spans) == 1:
                rate = spans.pop() / r
                break
        if rate is None:
            return None
        worst = rate if worst is None else max(worst, rate)
    return worst


def tail_mean(starts: Dict[str, Sequence[int]]) -> float:
    """When no steady rate shows: the largest mean interval between an
    actor's firings over the second half of them."""
    rates = []
    for ts in starts.values():
        if len(ts) >= 2:
            mid = len(ts) // 2
            rates.append((ts[-1] - ts[mid]) / max(1, len(ts) - 1 - mid))
    return max(rates) if rates else math.inf


def period_after(graph: dict, arch: dict, core_of, mem_of, capacity, firings: int) -> float:
    """Period of ``firings`` firings per actor: inf on deadlock, the steady
    rate, or the tail mean when none shows."""
    starts, dead = execute(graph, arch, core_of, mem_of, capacity, firings)
    if dead:
        return math.inf
    got = steady_period(starts)
    return got if got is not None else tail_mean(starts)


def simulated_period(graph: dict, arch: dict, core_of, mem_of, capacity,
                     first: int = 16, most: int = 128) -> float:
    """Period of a finished schedule: ``first`` firings per actor, doubled
    up to ``most`` while no steady rate shows."""
    n = first
    while True:
        starts, dead = execute(graph, arch, core_of, mem_of, capacity, n)
        if dead:
            return math.inf
        got = steady_period(starts)
        if got is not None:
            return got
        if n >= most:
            return tail_mean(starts)
        n = min(most, n * 2)
