"""Interior activation sharding constraints (Megatron-SP pattern).

The paper's channel-placement decision C_d pins each buffer to a memory;
these helpers are the TPU equivalent: they pin intermediate activations to
the intended mesh axes so GSPMD composes sequence-parallel residuals with
tensor-parallel attention/FFN interiors instead of fully gathering weight
matrices (observed at Nemotron scale: f32 [18432, 18432] full-weight
all-gathers when the interior layout was left to propagation).

All helpers are no-ops without an ambient mesh (smoke tests, pure-CPU
runs) and skip dims that don't divide their axis.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

__all__ = ["ambient_mesh", "constrain", "shard_heads", "shard_ffn", "shard_seq"]


def ambient_mesh():
    """The (abstract) mesh installed by ``jax.set_mesh``, or None — also
    inside ``shard_map``, whose Manual axes take no sharding constraint."""
    from jax.sharding import AxisType

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or any(t != AxisType.Auto for t in mesh.axis_types):
        return None
    return mesh


def _data_axes(mesh):
    dp = tuple(a for a in mesh.axis_names if a != "model")
    if not dp:
        return None
    return dp if len(dp) > 1 else dp[0]


def _size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def constrain(x: jnp.ndarray, *axes) -> jnp.ndarray:
    """with_sharding_constraint by axis names; dims that don't divide are
    silently replicated; no-op without a mesh."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    spec = []
    for dim, ax in enumerate(axes[: x.ndim]):
        if ax == "data":
            ax = _data_axes(mesh)
        if ax is not None and (
            ax not in mesh.axis_names and not isinstance(ax, tuple)
        ):
            ax = None
        n = _size(mesh, ax)
        if ax is None or n <= 1 or x.shape[dim] % n or x.shape[dim] < n:
            spec.append(None)
        else:
            spec.append(ax)
    spec += [None] * (x.ndim - len(spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, PartitionSpec(*spec)))


def shard_heads(x: jnp.ndarray, role: str = "q") -> jnp.ndarray:
    """[B, L, H, hd] (or [B, H, hd]) → heads over 'model', batch over data.

    When the head count does not divide the model axis (Nemotron/Gemma-2
    KV heads = 8, MusicGen = 24 on a 16-way axis):

      * role="q"  falls back to *sequence* sharding — queries stay local
        to their sequence shard;
      * role="kv" falls back to *replication* across the model axis — the
        K/V stream is read by every query shard, so it is gathered ONCE
        per layer here.  Leaving it sequence-sharded made the chunked
        attention's per-k-block dynamic slice re-gather the whole stack
        every scan step (observed at gemma2/train_4k: 3 × 1 GiB
        all-gathers × 2688 loop trips ≈ 8 TB of collective bytes per
        step — 40× the rest of the step combined).

    This is the paper's multi-reader insight as a sharding decision: the
    KV buffer has n_q_shard readers; one shared gather beats per-reader
    re-gathers."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    nm = mesh.shape.get("model", 1)
    if x.ndim == 4:
        B, L, H, hd = x.shape
        if H % nm == 0 and H >= nm:
            return constrain(x, "data", None, "model", None)
        if role == "kv":
            return constrain(x, "data", None, None, None)
        if L % nm == 0 and L >= nm and L > 1:
            return constrain(x, "data", "model", None, None)
        return constrain(x, "data", None, None, None)
    if x.ndim == 3:
        B, H, hd = x.shape
        if H % nm == 0 and H >= nm:
            return constrain(x, "data", "model", None)
    return constrain(x, "data", None, None)


def shard_ffn(x: jnp.ndarray) -> jnp.ndarray:
    """[B, L, F] → ffn hidden over 'model', batch over data."""
    return constrain(x, "data", None, "model")


def shard_seq(x: jnp.ndarray) -> jnp.ndarray:
    """[B, L, D] → sequence over 'model' (SP residual layout)."""
    return constrain(x, "data", "model", None)
