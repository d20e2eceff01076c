"""Model assembly for all assigned architectures.

One code path builds every family from :class:`ModelConfig`:

  * homogeneous blocks stacked along a layer axis and driven by
    ``jax.lax.scan`` (essential to keep 96-layer × d18432 compiles fast),
    with per-layer static flags (local/global attention) as scan inputs;
  * Zamba2-style hybrids scan over *groups*: a shared attention block whose
    parameters are stored once and multi-read by every invocation (the
    paper's MRB idea applied to parameters) followed by ``every`` Mamba2
    blocks;
  * decode threads a per-layer cache pytree (MRB ring KV buffers / SSM
    states) through the same scan.

Attention uses a memory-bounded chunked (flash-style, online-softmax)
implementation for long sequences and the direct quadratic reference for
short ones; both are numerically cross-checked in tests.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import (
    apply_rope,
    attention_decode,
    attention_fwd,
    embed_fwd,
    init_attention,
    init_cache,
    init_embed,
    init_mlp,
    init_norm,
    logits_fwd,
    make_attention_mask,
    mlp_fwd,
    norm_fwd,
    softcap,
)
from .moe import init_moe, moe_fwd
from .sharding_utils import ambient_mesh, shard_heads
from .ssm import init_ssm, init_ssm_state, ssm_decode, ssm_fwd

__all__ = [
    "init_model",
    "forward",
    "init_decode_state",
    "decode_step",
    "prefill",
    "prefill_step",
    "CHUNKED_ATTN_THRESHOLD",
]

CHUNKED_ATTN_THRESHOLD = 2048  # direct quadratic path below, chunked above
ATTN_Q_BLOCK = 512
ATTN_K_BLOCK = 1024
# §Perf: unroll the q-block loop so each q block statically scans only its
# causal prefix of k blocks — no upper-triangle waste.  Measured at
# gemma2-9b/prefill_32k: compute term 0.815→0.588 s, memory term
# 22.7→12.3 s, identical outputs (tests) — default ON; set False for the
# uniform-scan variant (smaller HLO, 2× attention waste).
ATTN_UNROLL_Q = True


def constrain_activation(x: jnp.ndarray) -> jnp.ndarray:
    """Pin activations to (batch over data, sequence over model) sharding
    when an ambient mesh is present (lowering under ``jax.set_mesh``).

    Without the batch constraint, GSPMD can lose the batch sharding through
    the embedding gather and carry fully replicated activations through the
    layer scan (observed: 74 GiB/device of saved residuals at
    qwen3/train_4k).  The sequence-parallel part shards the *stored*
    residuals 16× further (Megatron-SP style) — the all-gather back to full
    sequence happens inside the rematted block recompute, trading
    collective bytes for the dominant activation-memory term (observed:
    Nemotron-340B saved residuals 232 GiB → 15 GiB/device).  No-op outside
    a mesh context; dims that don't divide their axis stay unsharded."""
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = ambient_mesh()
    if mesh is None or x.ndim < 2:
        return x
    dp = tuple(a for a in mesh.axis_names if a != "model")
    if not dp:
        return x
    dsize = 1
    for a in dp:
        dsize *= mesh.shape[a]
    baxis = (dp if len(dp) > 1 else dp[0]) if x.shape[0] % dsize == 0 and x.shape[0] >= dsize else None
    saxis = None
    if (
        x.ndim >= 3
        and "model" in mesh.axis_names
        and x.shape[1] % mesh.shape["model"] == 0
        and x.shape[1] >= mesh.shape["model"]
        and x.shape[1] > 1
    ):
        saxis = "model"
    spec = PartitionSpec(*([baxis, saxis] + [None] * (x.ndim - 2)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# chunked (flash-style) attention — pure JAX online softmax
# ---------------------------------------------------------------------------
def attention_fwd_chunked(
    p: Dict,
    cfg: ModelConfig,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    window: jnp.ndarray,
) -> jnp.ndarray:
    """Causal (optionally sliding-window) self-attention with O(L·K_block)
    memory.  ``window`` is a traced scalar: ≥ L disables the window."""
    B, L, D = x.shape
    hd = cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    g = h // kv
    q = shard_heads((x @ p["wq"]).reshape(B, L, h, hd))
    k = shard_heads((x @ p["wk"]).reshape(B, L, kv, hd), role="kv")
    v = shard_heads((x @ p["wv"]).reshape(B, L, kv, hd), role="kv")
    if "q_norm" in p:
        from .layers import _rms

        q = _rms(q, p["q_norm"])
        k = _rms(k, p["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    scale = 1.0 / math.sqrt(hd)

    nq = L // ATTN_Q_BLOCK
    nk = L // ATTN_K_BLOCK
    qb = q.reshape(B, nq, ATTN_Q_BLOCK, kv, g, hd)
    kb = k.reshape(B, nk, ATTN_K_BLOCK, kv, hd)
    vb = v.reshape(B, nk, ATTN_K_BLOCK, kv, hd)

    def q_block(qi, q_i, n_kblocks=None):
        # online softmax over k blocks
        q_pos = qi * ATTN_Q_BLOCK + jnp.arange(ATTN_Q_BLOCK)

        def k_step(carry, kj):
            m, l, acc = carry
            k_j = jax.lax.dynamic_index_in_dim(kb, kj, axis=1, keepdims=False)
            v_j = jax.lax.dynamic_index_in_dim(vb, kj, axis=1, keepdims=False)
            k_pos = kj * ATTN_K_BLOCK + jnp.arange(ATTN_K_BLOCK)
            s = jnp.einsum(
                "bqkgd,bmkd->bkgqm", q_i, k_j, preferred_element_type=jnp.float32
            ) * scale
            s = softcap(s, cfg.attn_softcap)
            ok = (k_pos[None, :] <= q_pos[:, None]) & (
                q_pos[:, None] - k_pos[None, :] < window
            )
            s = jnp.where(ok[None, None, None, :, :], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            pexp = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + pexp.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqm,bmkd->bkgqd", pexp.astype(v_j.dtype), v_j
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, kv, g, ATTN_Q_BLOCK), -1e30, jnp.float32)
        l0 = jnp.zeros((B, kv, g, ATTN_Q_BLOCK), jnp.float32)
        a0 = jnp.zeros((B, kv, g, ATTN_Q_BLOCK, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_step, (m0, l0, a0), jnp.arange(n_kblocks if n_kblocks else nk)
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        # cast before stacking: the [nq, B, kv, g, Qb, hd] stack and its
        # reshape copies are 2× smaller in bf16 (−7 GiB at nemotron/prefill)
        return out.astype(x.dtype)  # [B, kv, g, Qb, hd]

    if ATTN_UNROLL_Q:
        # static per-q-block causal bound: block qi needs k blocks
        # 0 .. floor((qi·QBLK + QBLK − 1)/KBLK) — the upper triangle is
        # never computed (vs masked-out compute in the scanned variant)
        outs_list = []
        for qi in range(nq):
            hi = (qi * ATTN_Q_BLOCK + ATTN_Q_BLOCK - 1) // ATTN_K_BLOCK + 1
            outs_list.append(q_block(jnp.int32(qi), qb[:, qi], n_kblocks=hi))
        outs = jnp.stack(outs_list, axis=0)
    else:
        outs = jax.lax.map(lambda i: q_block(i, qb[:, i]), jnp.arange(nq))
    # [nq, B, kv, g, Qb, hd] -> [B, L, h*hd]
    out = jnp.moveaxis(outs, 0, 3).reshape(B, kv, g, L, hd)
    out = jnp.moveaxis(out.reshape(B, h, L, hd), 1, 2).reshape(B, L, h * hd)
    return out @ p["wo"]


# ---------------------------------------------------------------------------
# block init
# ---------------------------------------------------------------------------
def _init_block(rng: jax.Array, cfg: ModelConfig, kind: str) -> Dict:
    keys = jax.random.split(rng, 6)
    p: Dict[str, Any] = {"norm1": init_norm(cfg, cfg.d_model)}
    if kind == "s":
        p["ssm"] = init_ssm(keys[0], cfg)
        return p
    p["attn"] = init_attention(keys[0], cfg)
    if cfg.post_block_norm:
        p["post_norm1"] = init_norm(cfg, cfg.d_model)
        p["post_norm2"] = init_norm(cfg, cfg.d_model)
    if cfg.n_cond_tokens:
        p["norm_x"] = init_norm(cfg, cfg.d_model)
        p["xattn"] = init_attention(keys[1], cfg, cross=True)
    p["norm2"] = init_norm(cfg, cfg.d_model)
    if cfg.moe:
        p["moe"] = init_moe(keys[2], cfg)
    else:
        p["mlp"] = init_mlp(keys[2], cfg)
    return p


def _init_shared_block(rng: jax.Array, cfg: ModelConfig) -> Dict:
    """Zamba2 shared attention block: fuse(concat(x, x0)) → attn → mlp."""
    keys = jax.random.split(rng, 4)
    return {
        "fuse": jax.random.normal(keys[0], (2 * cfg.d_model, cfg.d_model), jnp.float32)
        / math.sqrt(2 * cfg.d_model),
        "norm1": init_norm(cfg, cfg.d_model),
        "attn": init_attention(keys[1], cfg),
        "norm2": init_norm(cfg, cfg.d_model),
        "mlp": init_mlp(keys[2], cfg),
        "out": jax.random.normal(keys[3], (cfg.d_model, cfg.d_model), jnp.float32)
        / math.sqrt(cfg.d_model),
    }


def init_model(rng: jax.Array, cfg: ModelConfig) -> Dict:
    kinds = cfg.layer_kinds()
    k_embed, k_blocks, k_shared, k_final = jax.random.split(rng, 4)
    params: Dict[str, Any] = {"embed": init_embed(k_embed, cfg)}

    layer_keys = jax.random.split(k_blocks, cfg.n_layers)
    ref_kind = kinds[0]
    # all layers share one structure (mixed kinds only differ by flags)
    stacked = jax.vmap(lambda k: _init_block(k, cfg, ref_kind))(layer_keys)
    if cfg.shared_attn_every:
        every = cfg.shared_attn_every
        n_groups = cfg.n_layers // every
        tail = cfg.n_layers - n_groups * every
        main = jax.tree_util.tree_map(
            lambda x: x[: n_groups * every].reshape((n_groups, every) + x.shape[1:]),
            stacked,
        )
        params["blocks"] = main
        if tail:
            params["tail"] = jax.tree_util.tree_map(
                lambda x: x[n_groups * every :], stacked
            )
        params["shared"] = _init_shared_block(k_shared, cfg)
    else:
        params["blocks"] = stacked
    params["final_norm"] = init_norm(cfg, cfg.d_model)

    # cast matmul weights to the compute dtype (norm scales stay f32)
    dt = jnp.dtype(cfg.dtype)

    def cast(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if x.dtype == jnp.float32 and x.ndim >= 2:
            return x.astype(dt)
        return x

    return jax.tree_util.tree_map_with_path(cast, params)


# ---------------------------------------------------------------------------
# forward (training / full-sequence)
# ---------------------------------------------------------------------------
def _block_fwd(
    p: Dict,
    cfg: ModelConfig,
    kind_is_ssm: bool,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    window: jnp.ndarray,
    cond: Optional[jnp.ndarray],
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decoder block.  Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = norm_fwd(p["norm1"], x)
    if kind_is_ssm:
        out = ssm_fwd(p["ssm"], cfg, h)
        return constrain_activation(x + out), aux
    L = x.shape[1]
    if L > CHUNKED_ATTN_THRESHOLD:
        # Pin the SP→full-seq gather HERE, on the bf16 normed tensor: left
        # to propagation, GSPMD gathers the f32 norm *internals* instead and
        # keeps multiple 4.8 GiB f32 full-seq copies alive (nemotron/prefill
        # buffer dumps: 6 × f32[2,32768,18432]).  The barrier stops the
        # simplifier from hoisting the bf16 cast back above the gather.
        from .sharding_utils import constrain

        h = jax.lax.optimization_barrier(h)
        h = constrain(h, "data", None, None)
        out = attention_fwd_chunked(p["attn"], cfg, h, positions, window)
    else:
        i = jnp.arange(L)[:, None]
        j = jnp.arange(L)[None, :]
        ok = (j <= i) & ((i - j) < window)
        mask = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)
        out = attention_fwd(p["attn"], cfg, h, positions, mask)
    if "post_norm1" in p:
        out = norm_fwd(p["post_norm1"], out)
    x = x + out
    if cond is not None and "xattn" in p:
        hx = norm_fwd(p["norm_x"], x)
        zero = jnp.zeros((x.shape[1], cond.shape[1]), jnp.float32)
        x = x + attention_fwd(p["xattn"], cfg, hx, positions, zero, kv_src=cond)
    h2 = norm_fwd(p["norm2"], x)
    if cfg.moe:
        out2, aux = moe_fwd(p["moe"], cfg, h2)
    else:
        out2 = mlp_fwd(p["mlp"], cfg, h2)
    if "post_norm2" in p:
        out2 = norm_fwd(p["post_norm2"], out2)
    return constrain_activation(x + out2), aux


def _shared_block_fwd(
    p: Dict, cfg: ModelConfig, x: jnp.ndarray, x0: jnp.ndarray,
    positions: jnp.ndarray, window: jnp.ndarray,
) -> jnp.ndarray:
    h = jnp.concatenate([x, x0], axis=-1) @ p["fuse"]
    h1 = norm_fwd(p["norm1"], h)
    L = x.shape[1]
    if L > CHUNKED_ATTN_THRESHOLD:
        a = attention_fwd_chunked(p["attn"], cfg, h1, positions, window)
    else:
        i = jnp.arange(L)[:, None]
        j = jnp.arange(L)[None, :]
        mask = jnp.where((j <= i) & ((i - j) < window), 0.0, -1e30).astype(jnp.float32)
        a = attention_fwd(p["attn"], cfg, h1, positions, mask)
    h = h + a
    h = h + mlp_fwd(p["mlp"], cfg, norm_fwd(p["norm2"], h))
    return x + h @ p["out"]


def _layer_windows(cfg: ModelConfig, L: int) -> jnp.ndarray:
    """Per-layer effective attention window for training (L+1 = unlimited).
    'l' layers are sliding-window; plain 'a' layers are windowed too when
    the arch uses SWA everywhere (e.g. Mixtral)."""
    wins = []
    for k in cfg.layer_kinds():
        windowed = cfg.sliding_window and k in ("l", "a")
        wins.append(cfg.sliding_window if windowed else L + 1)
    return jnp.asarray(wins, jnp.int32)


def forward(
    params: Dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    *,
    img_embeds: Optional[jnp.ndarray] = None,
    cond_embeds: Optional[jnp.ndarray] = None,
    return_hidden: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward.  Returns (logits, aux_loss) — or the final
    normed hidden states instead of logits when ``return_hidden`` (the
    training path computes the vocab projection chunked inside the loss to
    avoid materializing [B, L, V])."""
    x = embed_fwd(params["embed"], cfg, tokens)
    if img_embeds is not None:
        x = jnp.concatenate([img_embeds.astype(x.dtype), x], axis=1)
    x = constrain_activation(x)
    B, L, D = x.shape
    positions = jnp.arange(L)
    windows = _layer_windows(cfg, L)
    kinds = cfg.layer_kinds()
    is_ssm = kinds[0] == "s"
    cond = cond_embeds.astype(x.dtype) if cond_embeds is not None else None

    def block(x, p, window):
        return _block_fwd(p, cfg, is_ssm, x, positions, window, cond)

    if cfg.remat:
        block = jax.checkpoint(block)

    if cfg.shared_attn_every:
        x0 = x
        every = cfg.shared_attn_every
        shared = params["shared"]

        shared_train_win = jnp.int32(
            min(cfg.sliding_window, L + 1) if cfg.sliding_window else L + 1
        )

        def group_body(x, aux, gp, win_g):
            x = _shared_block_fwd(shared, cfg, x, x0, positions, shared_train_win)

            def inner(c, inp2):
                xi, auxi = c
                pi, wi = inp2
                xo, a = block(xi, pi, wi)
                return (xo, auxi + a), None

            (x, aux), _ = jax.lax.scan(inner, (x, aux), (gp, win_g))
            return x, aux

        if cfg.remat:
            # the shared block's activations must not be saved per group
            group_body = jax.checkpoint(group_body)

        def group(carry, inp):
            x, aux = carry
            gp, win_g = inp
            x, aux = group_body(x, aux, gp, win_g)
            return (x, aux), None

        n_groups = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
        win_groups = windows[: n_groups * every].reshape(n_groups, every)
        (x, aux), _ = jax.lax.scan(
            group, (x, jnp.zeros((), jnp.float32)), (params["blocks"], win_groups)
        )
        if "tail" in params:
            def inner_t(c, inp2):
                xi, auxi = c
                pi, wi = inp2
                xo, a = block(xi, pi, wi)
                return (xo, auxi + a), None

            (x, aux), _ = jax.lax.scan(
                inner_t, (x, aux), (params["tail"], windows[n_groups * every :])
            )
    else:
        def step(carry, inp):
            x, aux = carry
            p, window = inp
            x, a = block(x, p, window)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(
            step, (x, jnp.zeros((), jnp.float32)), (params["blocks"], windows)
        )

    x = norm_fwd(params["final_norm"], x)
    if return_hidden:
        return x, aux
    logits = logits_fwd(params["embed"], cfg, x)
    return logits, aux


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------
def init_decode_state(
    cfg: ModelConfig, batch: int, context: int, dtype=None
) -> Dict:
    """Per-layer cache stack: MRB ring KV buffers for attention layers
    (capacity = sliding window where bounded, else full context) or SSM
    states; hybrids carry one shared-attn cache per invocation site."""
    if dtype is None:
        dtype = jnp.dtype(cfg.dtype)
    kinds = cfg.layer_kinds()
    state: Dict[str, Any] = {}
    if kinds[0] == "s":
        one = init_ssm_state(cfg, batch)
        state["layers"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape), one
        )
    else:
        caps = [
            min(context, cfg.sliding_window)
            if (cfg.sliding_window and k in ("l", "a"))
            else context
            for k in kinds
        ]
        cap = max(caps)  # uniform capacity for stacking; masks bound windows
        one = init_cache(cfg, batch, cap, dtype)
        state["layers"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape), one
        )
    if cfg.shared_attn_every:
        n_inv = cfg.n_layers // cfg.shared_attn_every
        w = cfg.sliding_window or context
        one = init_cache(cfg, batch, min(context, w), dtype)
        state["shared"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n_inv,) + x.shape), one
        )
    return state


def decode_step(
    params: Dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    state: Dict,
    *,
    cond_embeds: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict]:
    """One decode step.  tokens: [B, 1] (or [B, K, 1] audio).  Returns
    (logits [B, 1, V] / [B, K, 1, V], new_state)."""
    x = embed_fwd(params["embed"], cfg, tokens)
    B = x.shape[0]
    kinds = cfg.layer_kinds()
    is_ssm = kinds[0] == "s"
    cond = cond_embeds.astype(x.dtype) if cond_embeds is not None else None
    # per-layer decode windows (0 = unlimited)
    dec_windows = jnp.asarray(
        [
            cfg.sliding_window if (cfg.sliding_window and k in ("l", "a")) else 0
            for k in kinds
        ],
        jnp.int32,
    )

    def block_step(x, p, cache, window):
        h = norm_fwd(p["norm1"], x)
        if is_ssm:
            out, cache = ssm_decode(p["ssm"], cfg, h, cache)
            return x + out, cache, None
        out, cache = attention_decode(p["attn"], cfg, h, cache, window)
        if "post_norm1" in p:
            out = norm_fwd(p["post_norm1"], out)
        x = x + out
        if cond is not None and "xattn" in p:
            hx = norm_fwd(p["norm_x"], x)
            zero = jnp.zeros((1, cond.shape[1]), jnp.float32)
            x = x + attention_fwd(
                p["xattn"], cfg, hx, jnp.arange(1), zero, kv_src=cond
            )
        h2 = norm_fwd(p["norm2"], x)
        if cfg.moe:
            out2, _ = moe_fwd(p["moe"], cfg, h2)
        else:
            out2 = mlp_fwd(p["mlp"], cfg, h2)
        if "post_norm2" in p:
            out2 = norm_fwd(p["post_norm2"], out2)
        return x + out2, cache, None

    if cfg.shared_attn_every:
        x0 = x
        every = cfg.shared_attn_every
        shared = params["shared"]
        n_groups = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
        shared_win = jnp.int32(cfg.sliding_window or 0)

        def shared_step(x, cache):
            h = jnp.concatenate([x, x0], axis=-1) @ shared["fuse"]
            h1 = norm_fwd(shared["norm1"], h)
            a, cache = attention_decode(shared["attn"], cfg, h1, cache, shared_win)
            h = h + a
            h = h + mlp_fwd(shared["mlp"], cfg, norm_fwd(shared["norm2"], h))
            return x + h @ shared["out"], cache

        def group(x, inp):
            gp, glayers, gshared, gwin = inp
            x, gshared = shared_step(x, gshared)

            def inner(xc, inp2):
                pi, ci, wi = inp2
                xo, co, _ = block_step(xc, pi, ci, wi)
                return xo, co

            x, glayers = jax.lax.scan(inner, x, (gp, glayers, gwin))
            return x, (glayers, gshared)

        layers_grouped = jax.tree_util.tree_map(
            lambda t: t[: n_groups * every].reshape((n_groups, every) + t.shape[1:]),
            state["layers"],
        )
        win_grouped = dec_windows[: n_groups * every].reshape(n_groups, every)
        x, (lg, sg) = jax.lax.scan(
            lambda xc, inp: group(xc, inp),
            x,
            (params["blocks"], layers_grouped, state["shared"], win_grouped),
        )
        new_layers = jax.tree_util.tree_map(
            lambda t: t.reshape((n_groups * every,) + t.shape[2:]), lg
        )
        if "tail" in params:
            tail_state = jax.tree_util.tree_map(
                lambda t: t[n_groups * every :], state["layers"]
            )

            def inner_t(xc, inp2):
                pi, ci, wi = inp2
                xo, co, _ = block_step(xc, pi, ci, wi)
                return xo, co

            x, tail_new = jax.lax.scan(
                inner_t, x, (params["tail"], tail_state, dec_windows[n_groups * every :])
            )
            new_layers = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b], 0), new_layers, tail_new
            )
        new_state = {"layers": new_layers, "shared": sg}
    else:
        def step(xc, inp):
            p, cache, window = inp
            xo, co, _ = block_step(xc, p, cache, window)
            return xo, co

        x, new_layers = jax.lax.scan(
            step, x, (params["blocks"], state["layers"], dec_windows)
        )
        new_state = {"layers": new_layers}

    x = norm_fwd(params["final_norm"], x)
    logits = logits_fwd(params["embed"], cfg, x)
    return logits, new_state


def prefill_step(
    params: Dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    *,
    img_embeds: Optional[jnp.ndarray] = None,
    cond_embeds: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Production prefill: full forward, returns the next-token logits
    (last position only — materializing [B, L, V] at 32k×256k would be
    absurd).  Decode cells exercise the cache machinery; see DESIGN.md."""
    kwargs = {}
    if img_embeds is not None:
        kwargs["img_embeds"] = img_embeds
    if cond_embeds is not None:
        kwargs["cond_embeds"] = cond_embeds
    hidden, _ = forward(params, cfg, tokens, return_hidden=True, **kwargs)
    last = hidden[:, -1:, :]
    return logits_fwd(params["embed"], cfg, last)


def prefill(
    params: Dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    context: int,
    *,
    img_embeds: Optional[jnp.ndarray] = None,
    cond_embeds: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict]:
    """Sequential prefill via decode steps (reference implementation used by
    equivalence tests; production prefill lowers `forward` + cache write)."""
    B = tokens.shape[0]
    L = tokens.shape[-1]
    state = init_decode_state(cfg, B, context)

    def one(i, carry):
        state, _ = carry
        tok = jax.lax.dynamic_slice_in_dim(tokens, i, 1, axis=-1)
        lg, state = decode_step(params, cfg, tok, state, cond_embeds=cond_embeds)
        return state, lg

    shape = (
        (B, cfg.n_codebooks, 1, cfg.vocab) if cfg.n_codebooks else (B, 1, cfg.vocab)
    )
    state, logits_last = jax.lax.fori_loop(
        0, L, one, (state, jnp.zeros(shape, jnp.float32))
    )
    return logits_last, state
