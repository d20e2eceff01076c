"""Self-timed schedule simulator (see README "Simulation subsystem").

Takes a decoded phenotype — ξ-transformed graph + architecture +
:class:`~repro.core.schedule.Schedule` — and *runs* it: actors fire when
input tokens and their bound core are available, reads/writes contend for
interconnects (and optionally MRB ports), and the steady-state iteration
interval is measured from the firing trace.  Three backends behind one
semantics (:mod:`repro.sim.model`):

* :func:`simulate` / :func:`simulate_period` — event-driven reference with
  per-resource Gantt traces (:class:`SimTrace`, rendered by
  :mod:`repro.sim.gantt`);
* :func:`batch_simulate` / :func:`batch_simulate_periods` — batched JAX
  backends sharing one fused actor-parallel round program: the
  ``vmap``-batched lax implementation (``backend="vectorized"``) and the
  Pallas actor-step kernel (``backend="pallas"``,
  :mod:`repro.kernels.sim_step`, interpret mode on the CPU; it does not
  compile for TPU yet) — wired into
  ``EvaluationEngine.evaluate_batch`` via ``sim_backend=``.

The ``sim_period`` objective (registered in :mod:`repro.core.problem`)
exposes the measured period to explorations; it falls back to the analytic
period when simulation is disabled here (:func:`set_simulation_enabled`,
or the ``REPRO_SIM_DISABLE`` environment variable).
"""
from __future__ import annotations

import os

from .events import Segment, SimResult, SimTrace, simulate, simulate_period
from .gantt import ascii_gantt, save_svg, svg_gantt
from .invariants import check_sim_invariants
from .model import (
    SimConfig,
    SimProgram,
    TaskSpec,
    contention_free,
    fallback_period,
    lower_phenotype,
    measure_period,
)
from .vectorized import (
    BATCH_BACKENDS,
    batch_simulate,
    batch_simulate_periods,
    trace_count,
)

__all__ = [
    "BATCH_BACKENDS",
    "trace_count",
    "SimConfig",
    "SimProgram",
    "TaskSpec",
    "Segment",
    "SimResult",
    "SimTrace",
    "simulate",
    "simulate_period",
    "batch_simulate",
    "batch_simulate_periods",
    "lower_phenotype",
    "measure_period",
    "fallback_period",
    "contention_free",
    "check_sim_invariants",
    "ascii_gantt",
    "svg_gantt",
    "save_svg",
    "simulation_enabled",
    "set_simulation_enabled",
]

_ENABLED = not bool(os.environ.get("REPRO_SIM_DISABLE"))


def simulation_enabled() -> bool:
    """Whether objectives backed by the simulator actually simulate."""
    return _ENABLED


def set_simulation_enabled(value: bool) -> bool:
    """Toggle simulation-backed objectives (``sim_period`` falls back to the
    analytic period while disabled).  Returns the previous value."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(value)
    return prev
