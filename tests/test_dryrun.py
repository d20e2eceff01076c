"""End-to-end dry-run machinery on a small forced-device mesh (subprocess:
the device count must be set before jax initializes)."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.configs import get_config, Shape
from repro.launch import dryrun
from repro.launch.mesh import make_mesh
import dataclasses

spec = get_config("qwen3-0.6b")
small = dataclasses.replace(
    spec,
    model=spec.smoke.replace(dtype="bfloat16"),
    smoke=spec.smoke,
)
mesh = make_mesh((2, 4), ("data", "model"))
shape = Shape("train_tiny", 64, 8, "train")
jitted, args = dryrun._train_cell(small, shape, mesh)
with jax.set_mesh(mesh):
    compiled = jitted.lower(*args).compile()
mem = compiled.memory_analysis()
from repro.launch.hlo import analyze_hlo
cost = analyze_hlo(compiled.as_text())
print(json.dumps({
    "devices": mesh.devices.size,
    "flops": cost.flops,
    "collective_bytes": cost.collective_bytes,
    "arg_bytes": int(mem.argument_size_in_bytes),
}))

# decode cell too
shape_d = Shape("decode_tiny", 64, 8, "decode")
jitted, args = dryrun._decode_cell(small, shape_d, mesh)
with jax.set_mesh(mesh):
    compiled = jitted.lower(*args).compile()
print(json.dumps({"decode_ok": True}))
"""


@pytest.mark.slow
def test_dryrun_cell_on_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.strip().splitlines() if l.startswith("{")]
    rec = json.loads(lines[0])
    assert rec["devices"] == 8
    assert rec["flops"] > 0
    assert rec["collective_bytes"] > 0  # gradient reductions must exist
    assert json.loads(lines[1])["decode_ok"]


def test_infeasible_mapping_inf_period_survives_json_save_load(tmp_path):
    """An infeasible decode (period math.inf, no schedule) must survive a
    dry-run style save/load cycle: the serialized result has ``schedule:
    null`` and deserializes back to an inf period that still orders last."""
    import math

    from conftest import make_pipelined_sobel
    from repro.core.caps_hms import DecodeResult, decode_via_heuristic
    from repro.core.ilp import ExactResult, decode_via_ilp

    gt, arch = make_pipelined_sobel()
    core = sorted(arch.cores)[0]
    ba = {a: core for a in gt.actors}
    cd = {c: "GLOBAL" for c in gt.channels}
    bad = decode_via_heuristic(gt, arch, cd, ba, max_period=1)
    bad_exact = decode_via_ilp(gt, arch, cd, ba, time_budget_s=0.5, max_period=1)
    good = decode_via_heuristic(gt, arch, cd, ba)
    assert not bad.feasible and not bad_exact.feasible and good.feasible

    path = tmp_path / "decodes.json"
    path.write_text(json.dumps({
        "bad": bad.to_json(),
        "bad_exact": bad_exact.to_json(),
        "good": good.to_json(),
    }))
    loaded = json.loads(path.read_text())
    lbad = DecodeResult.from_json(loaded["bad"])
    lbad_exact = ExactResult.from_json(loaded["bad_exact"])
    lgood = DecodeResult.from_json(loaded["good"])
    assert not lbad.feasible and lbad.schedule is None
    assert lbad.period == math.inf
    assert not lbad_exact.feasible and not lbad_exact.proven_optimal
    assert lbad_exact.period == math.inf
    assert lgood.feasible and lgood.period == good.period
    # math.inf (not a -1 sentinel): min() over periods picks the feasible one.
    assert min([lbad, lbad_exact, lgood], key=lambda r: r.period) is lgood
    # and the feasible schedule round-trips exactly
    assert lgood.schedule.to_json() == good.schedule.to_json()


def test_hlo_cost_model_scales_with_layers():
    """The loop-aware HLO cost model must multiply while bodies by trip
    count (XLA's flat cost_analysis does not — verified here)."""
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.hlo import analyze_hlo
    from repro.models.model import forward, init_model

    flops = {}
    for L in (2, 4):
        cfg = get_config("qwen3-0.6b").smoke.replace(n_layers=L)
        params_s = jax.eval_shape(lambda r: init_model(r, cfg), jax.random.PRNGKey(0))
        comp = (
            jax.jit(lambda p, t: forward(p, cfg, t)[0])
            .lower(params_s, jax.ShapeDtypeStruct((2, 64), jnp.int32))
            .compile()
        )
        flops[L] = analyze_hlo(comp.as_text()).flops
    # doubling layers must grow flops by well over the flat count
    assert flops[4] > 1.5 * flops[2] * 0.75
    assert flops[4] / flops[2] > 1.4
