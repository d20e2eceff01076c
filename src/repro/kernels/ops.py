"""Public kernel entry points with backend dispatch.

On TPU the Pallas kernels compile natively; everywhere else they run in
interpret mode (``interpret = not on_tpu()``, the default of every Pallas
entry point) or fall back to the jnp oracle.  ``use_pallas`` lets callers
force a path; tests sweep both and assert equality.
"""
from __future__ import annotations

from ..devices import on_tpu
from . import ref
from .decode_attention import mrb_decode_attention
from .mrb_ring import mrb_append

__all__ = ["ring_append", "ring_decode_attention", "on_tpu"]


def ring_append(buf, omega, token, *, use_pallas: bool = None, interpret: bool = None):
    """MRB ring append; see kernels.mrb_ring / kernels.ref."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        return ref.mrb_append_ref(buf, omega, token)
    return mrb_append(buf, omega, token, interpret=interpret)


def ring_decode_attention(
    q, buf_k, buf_v, t, *, window: int = 0, softcap: float = 0.0,
    use_pallas: bool = None, interpret: bool = None,
):
    """Multi-reader GQA decode attention; see kernels.decode_attention."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        return ref.decode_attention_ref(q, buf_k, buf_v, t, window, softcap)
    return mrb_decode_attention(
        q, buf_k, buf_v, t, window=window, softcap=softcap, interpret=interpret,
    )
