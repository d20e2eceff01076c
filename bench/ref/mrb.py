"""Multi-Reader Buffer (MRB) semantics (paper §II-C), as the verifier
checks a schedule's buffers.

An MRB c_m has one writer and multiple readers.  It keeps
  - a write index ω ∈ {0, …, γ−1}, and
  - per-reader read indices ρ_r ∈ {−1, 0, …, γ−1} (−1 ⇔ empty for r).

Available tokens from reader r's perspective:
    T(c_m, r) = 0                                   if ρ_r = −1
              = ((ω − ρ_r − 1) mod γ) + 1           otherwise
Free places from the writer's perspective:
    F(c_m) = γ − max_r T(c_m, r)

Firing the writer (producing ψ tokens): every ρ_r = −1 is set to ω, then
ω ← (ω + ψ) mod γ.  Firing reader r (consuming κ tokens):
    ρ_r ← −1                      if T(c_m, r) = κ      (r's view drained)
        ← (ρ_r + κ) mod γ         otherwise

:class:`MRBState` is the exact pure-Python index machine.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Tuple

__all__ = ["MRBState"]


# --------------------------------------------------------------------------
# Exact semantics (pure Python)
# --------------------------------------------------------------------------
@dataclass
class MRBState:
    """Paper-exact MRB index machine."""

    capacity: int                       # γ
    readers: Tuple[str, ...]            # reader ids
    write_index: int = 0                # ω
    read_index: Dict[str, int] = field(default_factory=dict)  # ρ_r

    def __post_init__(self) -> None:
        if self.capacity < 1:
            raise ValueError("MRB capacity must be >= 1")
        for r in self.readers:
            self.read_index.setdefault(r, -1)

    # T(c_m, a_r)
    def available(self, reader: str) -> int:
        rho = self.read_index[reader]
        if rho == -1:
            return 0
        return ((self.write_index - rho - 1) % self.capacity) + 1

    # F(c_m)
    def free(self) -> int:
        return self.capacity - max(self.available(r) for r in self.readers)

    def can_write(self, tokens: int = 1) -> bool:
        return self.free() >= tokens

    def can_read(self, reader: str, tokens: int = 1) -> bool:
        return self.available(reader) >= tokens

    def write(self, tokens: int = 1) -> None:
        """Fire the writer producing ``tokens`` (Eq. 4 then Eq. 5)."""
        if not self.can_write(tokens):
            raise RuntimeError("MRB overflow: writer fired without free places")
        for r in self.readers:
            if self.read_index[r] == -1:
                self.read_index[r] = self.write_index
        self.write_index = (self.write_index + tokens) % self.capacity

    def read(self, reader: str, tokens: int = 1) -> None:
        """Fire reader ``reader`` consuming ``tokens``."""
        if not self.can_read(reader, tokens):
            raise RuntimeError(f"MRB underflow for reader {reader!r}")
        if self.available(reader) == tokens:
            self.read_index[reader] = -1
        else:
            self.read_index[reader] = (self.read_index[reader] + tokens) % self.capacity

    def snapshot(self) -> Tuple[int, Dict[str, int]]:
        return self.write_index, dict(self.read_index)

