"""A periodic schedule (paper §III-C, §V): one start time per task,
repeating with period P, and the bindings and capacities of the
phenotype.  The verifier's input type."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Tuple

__all__ = ["TaskTimes", "Schedule"]


@dataclass
class TaskTimes:
    """Start times for all tasks of one iteration."""

    actor_start: Dict[str, int] = field(default_factory=dict)          # s_a
    read_start: Dict[Tuple[str, str], int] = field(default_factory=dict)   # s_(c,a)
    write_start: Dict[Tuple[str, str], int] = field(default_factory=dict)  # s_(a,c)


@dataclass
class Schedule:
    """A periodic schedule: the phenotype's timing part."""

    period: int
    times: TaskTimes
    actor_binding: Dict[str, str]
    channel_binding: Dict[str, str]
    capacities: Dict[str, int]  # possibly enlarged γ

    def to_json(self) -> Dict:
        """Plain-JSON form (edge keys become [channel, actor, start] rows)."""
        return {
            "period": self.period,
            "actor_start": dict(self.times.actor_start),
            "read_start": [[c, a, s] for (c, a), s in sorted(self.times.read_start.items())],
            "write_start": [[a, c, s] for (a, c), s in sorted(self.times.write_start.items())],
            "actor_binding": dict(self.actor_binding),
            "channel_binding": dict(self.channel_binding),
            "capacities": dict(self.capacities),
        }

    @classmethod
    def from_json(cls, d: Dict) -> "Schedule":
        return cls(
            period=d["period"],
            times=TaskTimes(
                actor_start=dict(d["actor_start"]),
                read_start={(c, a): s for c, a, s in d["read_start"]},
                write_start={(a, c): s for a, c, s in d["write_start"]},
            ),
            actor_binding=dict(d["actor_binding"]),
            channel_binding=dict(d["channel_binding"]),
            capacities=dict(d["capacities"]),
        )
