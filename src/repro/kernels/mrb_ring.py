"""Pallas TPU kernel: the paper's Multi-Reader Buffer as a KV ring cache.

The MRB write index ω becomes a *scalar-prefetch* operand: the BlockSpec
index map uses ω to select which capacity tile of the ring buffer is
brought into VMEM, so an append touches exactly one (BLK × H × d) tile
instead of the whole ring — HBM traffic C/BLK× lower than a naive
dynamic-update-slice over the gathered buffer.

Layout: buf [B, C, H, d] (capacity C ring per head), token [B, 1, H, d].
The tile is aligned for TPU: d is the lane dimension (multiple of 128
recommended), H·BLK rows map to sublanes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..devices import ensure_compile_cache, on_tpu

__all__ = ["mrb_append", "DEFAULT_BLOCK"]

DEFAULT_BLOCK = 256


def _append_kernel(omega_ref, buf_ref, tok_ref, out_ref, *, block: int):
    # copy the resident tile, then overwrite row ω mod BLK with the token
    out_ref[...] = buf_ref[...]
    row = omega_ref[0] % block
    out_ref[0, pl.dslice(row, 1), :, :] = tok_ref[0, :, :, :]


def mrb_append(
    buf: jnp.ndarray,
    omega: jnp.ndarray,
    token: jnp.ndarray,
    *,
    block: int = DEFAULT_BLOCK,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Write `token` at ring slot ω.  Returns the updated buffer.

    buf: [B, C, H, d]; omega: scalar int32; token: [B, 1, H, d].
    ``interpret`` defaults to ``not on_tpu()``.
    """
    if interpret is None:
        interpret = not on_tpu()
    ensure_compile_cache()
    return _append(buf, omega, token, block=block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _append(buf, omega, token, *, block: int, interpret: bool):
    B, C, H, d = buf.shape
    block = min(block, C)
    assert C % block == 0, f"capacity {C} must divide block {block}"
    grid = (B,)
    omega_arr = jnp.asarray(omega, jnp.int32).reshape(1)

    out = pl.pallas_call(
        functools.partial(_append_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, block, H, d), lambda b, om: (b, om[0] // block, 0, 0)
                ),
                pl.BlockSpec((1, 1, H, d), lambda b, om: (b, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, block, H, d), lambda b, om: (b, om[0] // block, 0, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        input_output_aliases={1: 0},  # buf tile aliases the output
        interpret=interpret,
    )(omega_arr, buf, token.astype(buf.dtype))
    return out
