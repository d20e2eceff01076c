"""Pallas kernel: the self-timed actor-step scan, one phenotype per cell.

The batched simulator's hot loop — ready-task selection, greedy
interconnect arbitration in scheduler priority order, core/interconnect
busy-until updates, and the MRB ω/ρ index advance — lowered as a Pallas
kernel.  The grid is the phenotype batch; each cell pulls its
binding-dependent operand block (durations, routes, core one-hots,
capacities) into VMEM once, runs the *entire* fused-scan simulation loop
with all state resident on-chip, and writes back only the (A, K_max)
firing-time table plus two scalars — on an accelerator the whole batch is
a single kernel launch with zero HBM round-trips between time steps,
where the stock XLA lowering re-materializes the loop carry every
iteration.

The step dynamics are not re-implemented here: the kernel body calls
:func:`repro.sim.vectorized.build_simulate_one`, the same single-element
program the lax backend vmaps, so the Pallas backend is bit-identical to
both siblings by construction (the parity suite asserts it anyway).  The
firing-count target ``K`` rides along as a scalar-prefetch operand, so
horizon-doubling reruns reuse the compiled kernel.

Off-TPU the kernel runs in interpret mode (pure JAX semantics).  On a
TPU the block layout passes the tiling rule (per-cell operands and the
``(dead, horizon)`` pair are 3-D blocks whose last two dims are full),
but Mosaic refuses the shared round body itself ("infer-vector-layout:
unsupported shape cast": the body broadcasts 1-D per-actor vectors into
2-D/3-D masks), so ``sim_backend="auto"`` routes TPU batches to the
``vectorized`` backend instead.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs
from ..devices import on_tpu

__all__ = ["build_pallas_sim"]


def build_pallas_sim(
    static,
    ports: Optional[int],
    k_max: int,
    *,
    interpret: Optional[bool] = None,
):
    """Compile the batched simulator as a Pallas kernel for one structure.

    Returns ``fn(tb, core_oh, gamma, K) -> (fire, dead, horizon)`` with
    the same contract as the lax backend: ``tb`` is the packed
    binding-derived task table, ``K`` is a runtime scalar, batch leads
    every operand, and outputs are ``(B, A, k_max)`` firing times,
    ``(B,)`` deadlock flags and ``(B,)`` horizons.
    """
    from ..sim.vectorized import build_simulate_one

    with obs.span("sim.pallas_build", k_max=int(k_max)):
        simulate_one, tables = build_simulate_one(static, ports, int(k_max))
    A, C, H, P, Tmax = (static[k] for k in ("A", "C", "H", "P", "Tmax"))
    K_MAX = int(k_max)
    if interpret is None:
        interpret = not on_tpu()
    obs.counter_add("sim.pallas_builds", interpret=bool(interpret))

    def kernel(k_ref, *refs):
        # refs: one per structure table (shared across cells), then the
        # per-cell batched operands, then the three outputs.
        table_refs = refs[: len(tables)]
        tb_ref, core_ref, gamma_ref, fire_ref, stat_ref = refs[len(tables):]
        fire, dead, horizon = simulate_one(
            tuple(r[...] for r in table_refs),
            tb_ref[0], core_ref[0], gamma_ref[0, 0], k_ref[0],
        )
        fire_ref[0] = fire
        stat_ref[0] = jnp.concatenate(
            [dead.astype(jnp.int32).reshape(1, 1), horizon.reshape(1, 1)], axis=1
        )

    def whole(tab):  # structure tables: same full block for every cell
        n = tab.ndim
        return pl.BlockSpec(tab.shape, lambda b, k, _n=n: (0,) * _n)

    def cell(b, k):  # every cell owns one phenotype's blocks
        return (b, 0, 0)

    @functools.partial(jax.jit, static_argnames=())
    def run(tb, core_oh, gamma, K):
        B = tb.shape[0]
        fire, stat = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B,),
                in_specs=[whole(tab) for tab in tables] + [
                    pl.BlockSpec((1, A, Tmax, 1 + H), lambda b, k: (b, 0, 0, 0)),
                    pl.BlockSpec((1, A, P), cell),
                    pl.BlockSpec((1, 1, C), cell),
                ],
                out_specs=[
                    pl.BlockSpec((1, A, K_MAX), cell),
                    pl.BlockSpec((1, 1, 2), cell),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((B, A, K_MAX), jnp.int32),
                jax.ShapeDtypeStruct((B, 1, 2), jnp.int32),
            ],
            interpret=interpret,
        )(
            jnp.asarray(K, jnp.int32).reshape(1),
            *[jnp.asarray(tab) for tab in tables],
            tb, core_oh, gamma[:, None, :],
        )
        return fire, stat[:, 0, 0].astype(bool), stat[:, 0, 1]

    return run
