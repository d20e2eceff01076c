"""The comparison that decides ``correct`` must fail what it is there to
catch.  Each test runs a cell through the harness on the CPU, skipping the
harness's look for a chip, and reads ``correct``.  The Multicamera cells
run at a small search size (population 8, 4 offspring); Sobel runs at its
configuration's own, since with 8 individuals its few decodes may all give
integral periods, on which float32 is exact and the control cannot show.

* sound, it is true;
* with the control (the reference at float32 in the program's place), it
  is false;
* with the timed path broken underneath, it is false, once for each fault
  the cell can have: a step that returns its state unchanged, half of the
  batch left out with the mean of the rest in its place, and an answer
  altered where it is produced.  (No cell runs across chips, so no
  exchange between chips can be left out.)
* with a tap that records nothing, it is false: every number and every
  sample the mix asks for is required.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402

SMALL = {
    "multicam.reference.relaxed": dict(population=8, offspring=4),
    "multicam.explore.exact": dict(population=8, offspring=4),
    "sobel.explore.relaxed": None,
}
# Long enough for each cell's sample: a fused Multicamera generation takes
# about 10 s on a CPU core.
SECONDS = {"multicam.reference.relaxed": 12.0, "multicam.explore.exact": 8.0,
           "sobel.explore.relaxed": 8.0}


def _run(cell: str, seed: int, **kw):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return harness.run_cell(spec, cell, seed, SECONDS[cell], False, t_start=time.perf_counter_ns(),
                            require_tpu=False, search=SMALL[cell], **kw)


# ------------------------------------------------------------------ faults
def _unchanged_gen(args, out):
    """The fused generation hands its parents back."""
    return np.asarray(args[1]), np.asarray(args[2])


def _half_gen(args, out):
    """Half of the survivors' objective vectors left out, the mean of the
    other half in their place."""
    F = np.array(out[1])
    k = max(1, len(F) // 2)
    F[k:] = F[:k].mean(axis=0)
    return np.asarray(out[0]), F


def _altered_gen(args, out):
    F = np.array(out[1])
    F[0, 0] *= 1.5
    return np.asarray(out[0]), F


def _unchanged_rank(args, out):
    """Truncation keeps the parents: the merged rows in their own order."""
    return np.arange(len(np.asarray(out)))


def _half_decode(args, out):
    out = np.array(out)
    k = max(1, len(out) // 2)
    out[k:] = out[:k].mean(axis=0)
    return out


def _altered_decode(args, out):
    out = np.array(out)
    out[0, 0] *= 1.5
    return out


def _unchanged_rank_crowd(objs, out):
    """Every row in front 0 with no crowding: the population never moves
    off its order."""
    return {i: 0 for i in out[0]}, {i: 0.0 for i in out[1]}


def _half_rank_crowd(objs, out):
    rank, crowd = out
    idx = sorted(crowd)
    fin = [crowd[i] for i in idx[: len(idx) // 2] if np.isfinite(crowd[i])]
    mean = float(np.mean(fin)) if fin else 0.0
    return rank, {i: (crowd[i] if n < len(idx) // 2 else mean) for n, i in enumerate(idx)}


def _altered_rank_crowd(objs, out):
    rank, crowd = dict(out[0]), out[1]
    rank[0] += 1
    return rank, crowd


CASES = {
    "multicam.reference.relaxed": {
        "unchanged": {"gen": _unchanged_gen},
        "half": {"gen": _half_gen},
        "altered": {"gen": _altered_gen},
    },
    "sobel.explore.relaxed": {
        "unchanged": {"rank": _unchanged_rank},
        "half": {"decode": _half_decode},
        "altered": {"decode": _altered_decode},
    },
    "multicam.explore.exact": {
        "unchanged": {"rank_crowd": _unchanged_rank_crowd},
        "half": {"rank_crowd": _half_rank_crowd},
        "altered": {"rank_crowd": _altered_rank_crowd},
    },
}


@pytest.mark.parametrize("cell", sorted(CASES))
def test_sound_run_is_correct(cell):
    out = _run(cell, 5)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cell", sorted(CASES))
def test_control_is_not_correct(cell):
    out = _run(cell, 6, control=True)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell,fault", [(c, f) for c in sorted(CASES) for f in CASES[c]])
def test_fault_is_not_correct(cell, fault):
    out = _run(cell, 7, faults=CASES[cell][fault])
    assert not out["correct"], out["checks"]


# ------------------------------------------------------------------- taps
# A later change may drive the timed path another way, so that a tap
# records nothing: the comparison must then fail, not pass on what is left.
TAPS = {
    "multicam.reference.relaxed": ["_plain"],
    "sobel.explore.relaxed": ["_plain", "_eval"],
    "multicam.explore.exact": ["_rank", "_batch"],
}
_PASS_THROUGH = {
    "_plain": lambda self, orig, fn, args, label: orig(fn, args, label),
    "_eval": lambda self, orig, fn, genes, label: orig(fn, genes, label),
    "_rank": lambda self, orig, objs: orig(objs),
    "_batch": lambda self, orig, genotypes: orig(genotypes),
}


@pytest.mark.parametrize("cell,tap", [(c, t) for c in sorted(TAPS) for t in TAPS[c]])
def test_silent_tap_is_not_correct(cell, tap, monkeypatch):
    monkeypatch.setattr(harness.Taps, tap, _PASS_THROUGH[tap])
    out = _run(cell, 8)
    assert not out["correct"], out["checks"]
    assert out["checks"]["samples_short"]["value"] > 0, out["checks"]
