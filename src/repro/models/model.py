"""Unified model definitions for all assigned architectures.

One functional model family with per-family layer bodies, all scanned over
stacked layer params (compile time independent of depth — required for the
80-layer dry-runs). Entry points:

    init_params(cfg, rng, dtype)      -> params pytree
    param_axes(cfg)                   -> same-structure pytree of logical dims
    forward(params, cfg, batch, ...)  -> logits          (training)
    prefill(params, cfg, batch, ...)  -> (logits, cache) (serving prefill)
    init_cache(cfg, batch, max_seq)   -> cache pytree    (zeros)
    cache_axes(cfg)                   -> logical dims for the cache
    decode_step(params, cfg, tokens, cache) -> (logits, cache)
"""
from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig, _expanded_pattern
from repro.distributed.sharding import logical
from repro.models import layers as L
from repro.models.scan_ctl import scan as _ctl_scan

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def _init(rng, shape, scale, dtype):
    return (jax.random.normal(rng, shape, dtype=jnp.float32) * scale).astype(dtype)


def _attn_layer_shapes(cfg: ModelConfig, cross: bool = False,
                       moe: Optional[bool] = None) -> Dict[str, Tuple[int, ...]]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    if moe is None:
        moe = bool(cfg.num_experts)
    # separate q/k/v projections: fused QKV splits at non-shard-aligned
    # boundaries under TP and GSPMD realigns with collective-permutes
    # (EXPERIMENTS.md §Perf E1)
    s: Dict[str, Tuple[int, ...]] = {
        "ln1": (d,),
        "wq": (d, H * hd),
        "wk": (d, K * hd),
        "wv": (d, K * hd),
        "wo": (H * hd, d),
    }
    if cfg.qkv_bias:
        s["bq"] = (H * hd,)
        s["bk"] = (K * hd,)
        s["bv"] = (K * hd,)
    if cfg.family == "audio":
        s["ln1_b"] = (d,)
    if cross:
        s["ln_x"] = (d,)
        s["ln_x_b"] = (d,)
        s["wq_x"] = (d, H * hd)
        s["wk_x"] = (d, K * hd)
        s["wv_x"] = (d, K * hd)
        s["wo_x"] = (H * hd, d)
    # FFN
    if moe:
        s["router"] = (d, cfg.num_experts)
        s["wi"] = (cfg.num_experts, d, 2 * cfg.d_ff)
        s["wd"] = (cfg.num_experts, cfg.d_ff, d)
        s["ln2"] = (d,)
    elif cfg.family == "audio":
        s.update({"ln2": (d,), "ln2_b": (d,), "wi": (d, cfg.d_ff), "bi": (cfg.d_ff,),
                  "wd": (cfg.d_ff, d), "bd": (d,)})
    else:
        ff = cfg.d_ff_dense if (cfg.num_experts and cfg.d_ff_dense) else cfg.d_ff
        # gate|up as an explicit (2, F) axis so the split is shard-aligned
        s.update({"ln2": (d,), "wi": (d, 2, ff), "wd": (ff, d)})
    return s


def _attn_layer_axes(cfg: ModelConfig, cross: bool = False,
                     moe: Optional[bool] = None) -> Dict[str, Tuple]:
    if moe is None:
        moe = bool(cfg.num_experts)
    ax: Dict[str, Tuple] = {
        "ln1": (None,),
        "wq": ("d_model", "heads_x_hd"),
        # K/V projections replicate when kv_heads < TP degree (small params;
        # avoids mid-head sharding reshards)
        "wk": ("d_model", "kv_x_hd"),
        "wv": ("d_model", "kv_x_hd"),
        "wo": ("heads_x_hd", "d_model"),
    }
    if cfg.qkv_bias:
        ax["bq"] = ("heads_x_hd",)
        ax["bk"] = ("kv_x_hd",)
        ax["bv"] = ("kv_x_hd",)
    if cfg.family == "audio":
        ax["ln1_b"] = (None,)
    if cross:
        ax.update({"ln_x": (None,), "ln_x_b": (None,),
                   "wq_x": ("d_model", "heads_x_hd"),
                   "wk_x": ("d_model", "kv_x_hd"),
                   "wv_x": ("d_model", "kv_x_hd"),
                   "wo_x": ("heads_x_hd", "d_model")})
    if moe:
        ax.update({"router": ("d_model", None),
                   "wi": ("experts", "d_model", "ff"),
                   "wd": ("experts", "ff", "d_model"),
                   "ln2": (None,)})
    elif cfg.family == "audio":
        ax.update({"ln2": (None,), "ln2_b": (None,), "wi": ("d_model", "ff"),
                   "bi": ("ff",), "wd": ("ff", "d_model"), "bd": (None,)})
    else:
        ax.update({"ln2": (None,), "wi": ("d_model", None, "ff"),
                   "wd": ("ff", "d_model")})
    return ax


def _rglru_layer_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    d, lw = cfg.d_model, cfg.lru_width or cfg.d_model
    return {
        "ln1": (d,),
        "w_x": (d, lw), "w_gate": (d, lw),
        "conv_w": (lw, cfg.ssm_conv_width), "conv_b": (lw,),
        "a_param": (lw,), "w_rg": (lw, lw), "w_ig": (lw, lw),
        "w_y": (lw, d),
        "ln2": (d,), "wi": (d, 2, cfg.d_ff), "wd": (cfg.d_ff, d),
    }


def _rglru_layer_axes(cfg: ModelConfig) -> Dict[str, Tuple]:
    return {
        "ln1": (None,),
        "w_x": ("d_model", "lru"), "w_gate": ("d_model", "lru"),
        "conv_w": ("lru", None), "conv_b": ("lru",),
        "a_param": ("lru",), "w_rg": ("lru", "lru"), "w_ig": ("lru", "lru"),
        "w_y": ("lru", "d_model"),
        "ln2": (None,), "wi": ("d_model", None, "ff"), "wd": ("ff", "d_model"),
    }


def _ssm_layer_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    d, din, N, nh, W = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                        cfg.ssm_heads, cfg.ssm_conv_width)
    return {
        "ln": (d,),
        "in_proj": (d, 2 * din + 2 * N + nh),
        "conv_w": (din + 2 * N, W), "conv_b": (din + 2 * N,),
        "A_log": (nh,), "Dp": (nh,), "dt_bias": (nh,),
        "norm_w": (din,),
        "out_proj": (din, d),
    }


def _ssm_layer_axes(cfg: ModelConfig) -> Dict[str, Tuple]:
    return {
        "ln": (None,),
        "in_proj": ("d_model", "ssm_inner"),
        "conv_w": ("ssm_inner", None), "conv_b": ("ssm_inner",),
        "A_log": (None,), "Dp": (None,), "dt_bias": (None,),
        "norm_w": ("ssm_inner",),
        "out_proj": ("ssm_inner", "d_model"),
    }


def _stack_shapes(shapes: Dict[str, Tuple[int, ...]], n: int):
    return {k: (n,) + v for k, v in shapes.items()}


def _stack_axes(axes: Dict[str, Tuple], n_name: str = "layers"):
    return {k: (n_name,) + v for k, v in axes.items()}


def _hybrid_counts(cfg: ModelConfig) -> Tuple[int, int]:
    """(#scanned triples, #tail rglru layers) such that the expanded pattern
    (rglru, rglru, attn)* truncated to num_layers is realized exactly."""
    pat = _expanded_pattern(cfg)
    n_tri = len(pat) // 3
    tail = len(pat) - 3 * n_tri
    assert all(p == "rglru" for p in pat[3 * n_tri:]), "tail must be rglru layers"
    return n_tri, tail


def model_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    out: Dict[str, Any] = {"embed": (cfg.vocab_size, d), "final_norm": (d,)}
    if not cfg.tie_embeddings:
        out["unembed"] = (d, cfg.vocab_size)

    if cfg.family == "ssm":
        out["layers"] = _stack_shapes(_ssm_layer_shapes(cfg), cfg.num_layers)
    elif cfg.family == "hybrid":
        n_tri, tail = _hybrid_counts(cfg)
        tri = {"r1": _rglru_layer_shapes(cfg), "r2": _rglru_layer_shapes(cfg),
               "attn": _attn_layer_shapes(cfg)}
        out["blocks"] = jax.tree.map(lambda s: (n_tri,) + s, tri,
                                     is_leaf=lambda x: isinstance(x, tuple))
        if tail:
            out["tail"] = _stack_shapes(_rglru_layer_shapes(cfg), tail)
    elif cfg.family == "audio":
        out["enc_final_norm"] = (d,)
        out["enc_final_norm_b"] = (d,)
        out["final_norm_b"] = (d,)
        out["enc_layers"] = _stack_shapes(_attn_layer_shapes(cfg), cfg.num_encoder_layers)
        out["layers"] = _stack_shapes(_attn_layer_shapes(cfg, cross=True), cfg.num_layers)
    elif cfg.num_experts and cfg.moe_layer_freq == 2:
        n_pairs = cfg.num_layers // 2
        pair = {"dense": _attn_layer_shapes(cfg, moe=False),
                "moe": _attn_layer_shapes(cfg, moe=True)}
        out["pairs"] = jax.tree.map(lambda s: (n_pairs,) + s, pair,
                                    is_leaf=lambda x: isinstance(x, tuple))
    else:  # dense / moe(freq=1) / vlm
        out["layers"] = _stack_shapes(_attn_layer_shapes(cfg), cfg.num_layers)
    return out


def param_axes(cfg: ModelConfig) -> Dict[str, Any]:
    out: Dict[str, Any] = {"embed": ("vocab", "d_model"), "final_norm": (None,)}
    if not cfg.tie_embeddings:
        out["unembed"] = ("d_model", "vocab")
    if cfg.family == "ssm":
        out["layers"] = _stack_axes(_ssm_layer_axes(cfg))
    elif cfg.family == "hybrid":
        tri = {"r1": _rglru_layer_axes(cfg), "r2": _rglru_layer_axes(cfg),
               "attn": _attn_layer_axes(cfg)}
        out["blocks"] = jax.tree.map(lambda a: ("layers",) + a, tri,
                                     is_leaf=lambda x: isinstance(x, tuple))
        if _hybrid_counts(cfg)[1]:
            out["tail"] = _stack_axes(_rglru_layer_axes(cfg))
    elif cfg.family == "audio":
        out["enc_final_norm"] = (None,)
        out["enc_final_norm_b"] = (None,)
        out["final_norm_b"] = (None,)
        out["enc_layers"] = _stack_axes(_attn_layer_axes(cfg))
        out["layers"] = _stack_axes(_attn_layer_axes(cfg, cross=True))
    elif cfg.num_experts and cfg.moe_layer_freq == 2:
        pair = {"dense": _attn_layer_axes(cfg, moe=False),
                "moe": _attn_layer_axes(cfg, moe=True)}
        out["pairs"] = jax.tree.map(lambda a: ("layers",) + a, pair,
                                    is_leaf=lambda x: isinstance(x, tuple))
    else:
        out["layers"] = _stack_axes(_attn_layer_axes(cfg))
    return out


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    shapes = model_shapes(cfg)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    rngs = jax.random.split(rng, len(leaves))

    def init_one(shape, key):
        if len(shape) >= 3 and shape[-2] == 2:     # (.., d, 2, F) gate|up
            fan_in = shape[-3]
        elif len(shape) >= 2:
            fan_in = shape[-2]
        else:
            fan_in = shape[-1]
        scale = 1.0 / math.sqrt(max(fan_in, 1))
        if len(shape) == 1 or shape[-1] == 1:
            return jnp.zeros(shape, dtype=dtype)
        return _init(key, shape, scale, dtype)

    params = treedef.unflatten([init_one(s, k) for s, k in zip(leaves, rngs)])

    # family-specific non-zero inits
    def fix(layer):
        if "A_log" in layer:
            nh = layer["A_log"].shape[-1]
            a0 = jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32))
            layer["A_log"] = jnp.broadcast_to(a0, layer["A_log"].shape).astype(dtype)
            layer["dt_bias"] = jnp.full_like(layer["dt_bias"], 0.5)
            layer["Dp"] = jnp.ones_like(layer["Dp"])
        if "a_param" in layer:
            layer["a_param"] = jnp.full_like(layer["a_param"], 0.7)
        return layer

    if cfg.family == "ssm":
        params["layers"] = fix(params["layers"])
    elif cfg.family == "hybrid":
        params["blocks"]["r1"] = fix(params["blocks"]["r1"])
        params["blocks"]["r2"] = fix(params["blocks"]["r2"])
        if "tail" in params:
            params["tail"] = fix(params["tail"])
    return params


def param_count(params: Params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


def params_device(params: Params) -> jax.Device:
    """The device holding a single-device params pytree: a serving instance
    allocates its state there and runs its worker threads `on_device` of
    it. Params that hold no device array (a stub model) place nothing, so
    they get the default device."""
    leaves = jax.tree.leaves(params)
    if not leaves or not isinstance(leaves[0], jax.Array):
        return jax.devices()[0]
    return next(iter(leaves[0].devices()))


def on_device(device: jax.Device):
    """Context that places new arrays on `device`. A no-op on the process's
    default device: entering `jax.default_device` changes jit's cache key,
    so a program warmed up outside the context would compile again."""
    if device == jax.devices()[0]:
        return contextlib.nullcontext()
    return jax.default_device(device)


# ---------------------------------------------------------------------------
# Layer bodies
# ---------------------------------------------------------------------------


def _project_qkv(cfg: ModelConfig, p: Params, x: jax.Array):
    """Three shard-aligned projections (see §Perf E1)."""
    B, S = x.shape[:2]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    k = jnp.einsum("bsd,dq->bsq", x, p["wk"])
    v = jnp.einsum("bsd,dq->bsq", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(B, S, H, hd), k.reshape(B, S, K, hd),
            v.reshape(B, S, K, hd))


def attn_block(cfg: ModelConfig, p: Params, h: jax.Array, *, positions,
               attn_impl: str = "auto", window: int = 0,
               use_rope: bool = True, causal: bool = True):
    """Pre-norm attention block. Returns (h, (k, v)) — k/v for cache collection."""
    if cfg.family == "audio":
        x = L.layer_norm(h, p["ln1"], p["ln1_b"], cfg.norm_eps)
    else:
        x = L.rms_norm(h, p["ln1"], cfg.norm_eps)
    q, k, v = _project_qkv(cfg, p, x)
    if use_rope:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    q = logical(q, "act_batch", "act_seq", "act_heads", None)
    k = logical(k, "act_batch", "act_seq", "act_heads", None)
    o = L.attention(q, k, v, impl=attn_impl, causal=causal, local_window=window)
    o = o.reshape(h.shape[0], h.shape[1], -1)
    h = h + jnp.einsum("bsq,qd->bsd", o, p["wo"])
    return logical(h, "act_batch", "act_seq", "act_d"), (k, v)


def ffn_block(cfg: ModelConfig, p: Params, h: jax.Array):
    """FFN variant dispatches on the param keys of the layer (supports
    interleaved dense/MoE stacks where cfg alone is ambiguous)."""
    if "ln2_b" in p:                     # audio: LayerNorm + GELU MLP
        x = L.layer_norm(h, p["ln2"], p["ln2_b"], cfg.norm_eps)
        y = L.gelu_mlp(x, p["wi"], p["bi"], p["wd"], p["bd"])
    elif "router" in p:                  # MoE
        x = L.rms_norm(h, p["ln2"], cfg.norm_eps)
        y = L.moe_ffn(x, p["router"], p["wi"], p["wd"],
                      k=cfg.experts_per_token,
                      capacity_factor=cfg.moe_capacity_factor,
                      min_capacity=cfg.moe_min_capacity)
    else:                                # SwiGLU
        x = L.rms_norm(h, p["ln2"], cfg.norm_eps)
        y = L.swiglu(x, p["wi"], p["wd"])
    return logical(h + y, "act_batch", "act_seq", "act_d")


def rglru_block(cfg: ModelConfig, p: Params, h: jax.Array, *,
                conv_state=None, h_state=None):
    """Griffin recurrent block + MLP. Returns (h, (h_last, conv_state))."""
    x = L.rms_norm(h, p["ln1"], cfg.norm_eps)
    gate = jax.nn.gelu(jnp.einsum("bsd,dc->bsc", x, p["w_gate"]))
    xb = jnp.einsum("bsd,dc->bsc", x, p["w_x"])
    xb, conv_state = L.causal_conv1d(xb, p["conv_w"], p["conv_b"], conv_state)
    y, h_last = L.rglru(xb, p["a_param"], p["w_rg"], p["w_ig"], h_state)
    h = h + jnp.einsum("bsc,cd->bsd", y * gate, p["w_y"])
    h = ffn_block(cfg, p, h)
    return logical(h, "act_batch", "act_seq", "act_d"), (h_last, conv_state)


def ssm_block(cfg: ModelConfig, p: Params, h: jax.Array, *,
              conv_state=None, ssm_state=None):
    """Mamba2 block. Returns (h, (ssm_state, conv_state))."""
    din, N, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    P_ = cfg.ssm_head_dim
    x = L.rms_norm(h, p["ln"], cfg.norm_eps)
    proj = jnp.einsum("bsd,dp->bsp", x, p["in_proj"])
    # layout: z (din) | xBC (din + 2N, the conv input x|B|C) | dt (nh)
    z, xBC, dt = jnp.split(proj, [din, 2 * din + 2 * N], axis=-1)
    xBC_conv, conv_state = L.causal_conv1d(xBC, p["conv_w"], p["conv_b"], conv_state)
    xBC_conv = jax.nn.silu(xBC_conv)
    xs, Bm, Cm = jnp.split(xBC_conv, [din, din + N], axis=-1)
    Bsz, S = h.shape[:2]
    xs = xs.reshape(Bsz, S, nh, P_)
    dt_sp = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, ssm_state = L.ssd_chunked(xs, dt_sp, A, Bm, Cm, chunk=cfg.ssm_chunk, h0=ssm_state)
    y = y + xs * p["Dp"][None, None, :, None]
    y = y.reshape(Bsz, S, din)
    y = L.rms_norm(y * jax.nn.silu(z), p["norm_w"], cfg.norm_eps)
    h = h + jnp.einsum("bsc,cd->bsd", y, p["out_proj"])
    return logical(h, "act_batch", "act_seq", "act_d"), (ssm_state, conv_state)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_tokens(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    h = params["embed"][tokens]
    return logical(h, "act_batch", "act_seq", "act_d")


def lm_head(cfg: ModelConfig, params: Params, h: jax.Array,
            norm_key: str = "final_norm") -> jax.Array:
    if cfg.family == "audio":
        h = L.layer_norm(h, params["final_norm"], params["final_norm_b"], cfg.norm_eps)
    else:
        h = L.rms_norm(h, params[norm_key], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum("bsd,dv->bsv", h, w)
    return logical(logits, "act_batch", "act_seq", "act_vocab")


def _merge_vision(cfg: ModelConfig, h: jax.Array, vision_embeds: jax.Array):
    """Replace the leading num_patches positions with patch embeddings."""
    P_ = vision_embeds.shape[1]
    return h.at[:, :P_, :].set(vision_embeds.astype(h.dtype))


# ---------------------------------------------------------------------------
# Forward (training) — full sequence, scan over layers
# ---------------------------------------------------------------------------


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn)
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    raise ValueError(policy)


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            attn_impl: str = "auto", remat: str = "none") -> jax.Array:
    """Full-sequence forward -> logits (B, S, V)."""
    if cfg.family == "audio":
        return _forward_audio(params, cfg, batch, attn_impl=attn_impl, remat=remat)

    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.arange(S)[None, :]
    h = embed_tokens(cfg, params, tokens)
    if cfg.family == "vlm":
        h = _merge_vision(cfg, h, batch["vision_embeds"])

    if cfg.family == "ssm":
        def body(carry, p_l):
            y, _ = ssm_block(cfg, p_l, carry)
            return y, None
        h, _ = _ctl_scan(_remat(body, remat), h, params["layers"])
    elif cfg.family == "hybrid":
        def tri_body(carry, p_t):
            y, _ = rglru_block(cfg, p_t["r1"], carry)
            y, _ = rglru_block(cfg, p_t["r2"], y)
            y, _ = attn_block(cfg, p_t["attn"], y, positions=positions,
                              attn_impl=attn_impl, window=cfg.local_window)
            y = ffn_block(cfg, p_t["attn"], y)
            return y, None
        h, _ = _ctl_scan(_remat(tri_body, remat), h, params["blocks"])
        if "tail" in params:
            def tail_body(carry, p_l):
                y, _ = rglru_block(cfg, p_l, carry)
                return y, None
            h, _ = _ctl_scan(_remat(tail_body, remat), h, params["tail"])
    elif "pairs" in params:
        def pair_body(carry, p_p):
            y = carry
            for sub in ("dense", "moe"):
                y, _ = attn_block(cfg, p_p[sub], y, positions=positions,
                                  attn_impl=attn_impl, window=cfg.local_window)
                y = ffn_block(cfg, p_p[sub], y)
            return y, None
        h, _ = _ctl_scan(_remat(pair_body, remat), h, params["pairs"])
    else:
        def body(carry, p_l):
            y, _ = attn_block(cfg, p_l, carry, positions=positions,
                              attn_impl=attn_impl, window=cfg.local_window)
            y = ffn_block(cfg, p_l, y)
            return y, None
        h, _ = _ctl_scan(_remat(body, remat), h, params["layers"])

    return lm_head(cfg, params, h)


def _forward_audio(params, cfg, batch, *, attn_impl="auto", remat="none"):
    frames = batch["frames"]                       # (B, Tenc, D) stub embeddings
    tokens = batch["tokens"]                       # (B, S)
    B, Tenc = frames.shape[:2]
    S = tokens.shape[1]

    # --- encoder (bidirectional) ---
    h = frames + L.sinusoidal_positions(Tenc, cfg.d_model)[None].astype(frames.dtype)
    h = logical(h, "act_batch", "act_seq", "act_d")
    enc_pos = jnp.arange(Tenc)[None, :]

    def enc_body(carry, p_l):
        y, _ = attn_block(cfg, p_l, carry, positions=enc_pos, attn_impl=attn_impl,
                          use_rope=False, causal=False)
        y = ffn_block(cfg, p_l, y)
        return y, None
    h, _ = _ctl_scan(_remat(enc_body, remat), h, params["enc_layers"])
    enc_out = L.layer_norm(h, params["enc_final_norm"], params["enc_final_norm_b"],
                           cfg.norm_eps)

    # --- decoder (causal self-attn + cross-attn) ---
    hd_ = embed_tokens(cfg, params, tokens)
    hd_ = hd_ + L.sinusoidal_positions(S, cfg.d_model)[None].astype(hd_.dtype)
    dec_pos = jnp.arange(S)[None, :]
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    H = cfg.num_heads

    def dec_body(carry, p_l):
        y, _ = attn_block(cfg, p_l, carry, positions=dec_pos, attn_impl=attn_impl,
                          use_rope=False, causal=True)
        # cross attention
        x = L.layer_norm(y, p_l["ln_x"], p_l["ln_x_b"], cfg.norm_eps)
        q = jnp.einsum("bsd,dq->bsq", x, p_l["wq_x"]).reshape(B, S, H, hd)
        xk = jnp.einsum("btd,dq->btq", enc_out, p_l["wk_x"]).reshape(B, Tenc, K, hd)
        xv = jnp.einsum("btd,dq->btq", enc_out, p_l["wv_x"]).reshape(B, Tenc, K, hd)
        o = L.attention(q, xk, xv, impl=attn_impl, causal=False)
        y = y + jnp.einsum("bsq,qd->bsd", o.reshape(B, S, -1), p_l["wo_x"])
        y = ffn_block(cfg, p_l, y)
        return y, None

    hd_, _ = _ctl_scan(_remat(dec_body, remat), hd_, params["layers"])
    return lm_head(cfg, params, hd_)


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------


def cache_shapes(cfg: ModelConfig, batch: int, max_seq: int,
                 dtype=jnp.bfloat16) -> Dict[str, Tuple]:
    """Returns dict name -> (shape, dtype)."""
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    out: Dict[str, Tuple] = {"pos": ((), jnp.int32)}
    if cfg.family == "ssm":
        din, N, nh, W = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv_width
        out["ssm"] = ((cfg.num_layers, batch, nh, cfg.ssm_head_dim, N), jnp.float32)
        out["conv"] = ((cfg.num_layers, batch, W - 1, din + 2 * N), dtype)
    elif cfg.family == "hybrid":
        n_tri, tail = _hybrid_counts(cfg)
        lw, W = cfg.lru_width or cfg.d_model, cfg.ssm_conv_width
        win = min(cfg.local_window or max_seq, max_seq)
        out["k"] = ((n_tri, batch, win, K, hd), dtype)
        out["v"] = ((n_tri, batch, win, K, hd), dtype)
        out["h1"] = ((n_tri, batch, lw), jnp.float32)
        out["h2"] = ((n_tri, batch, lw), jnp.float32)
        out["conv1"] = ((n_tri, batch, W - 1, lw), dtype)
        out["conv2"] = ((n_tri, batch, W - 1, lw), dtype)
        if tail:
            out["h_tail"] = ((tail, batch, lw), jnp.float32)
            out["conv_tail"] = ((tail, batch, W - 1, lw), dtype)
    elif cfg.family == "audio":
        out["k"] = ((cfg.num_layers, batch, max_seq, K, hd), dtype)
        out["v"] = ((cfg.num_layers, batch, max_seq, K, hd), dtype)
        out["xk"] = ((cfg.num_layers, batch, cfg.encoder_seq, K, hd), dtype)
        out["xv"] = ((cfg.num_layers, batch, cfg.encoder_seq, K, hd), dtype)
    else:
        lead = ((cfg.num_layers // 2, 2) if cfg.num_experts and cfg.moe_layer_freq == 2
                else (cfg.num_layers,))
        out["k"] = (lead + (batch, max_seq, K, hd), dtype)
        out["v"] = (lead + (batch, max_seq, K, hd), dtype)
    return out


def cache_axes(cfg: ModelConfig) -> Dict[str, Tuple]:
    ax: Dict[str, Tuple] = {"pos": ()}
    if cfg.family == "ssm":
        ax["ssm"] = ("layers", "cache_batch", None, "ssm_inner", None)
        ax["conv"] = ("layers", "cache_batch", None, "ssm_inner")
    elif cfg.family == "hybrid":
        ax["k"] = ("layers", "cache_batch", "cache_seq", "cache_kv_heads", None)
        ax["v"] = ("layers", "cache_batch", "cache_seq", "cache_kv_heads", None)
        for k in ("h1", "h2"):
            ax[k] = ("layers", "cache_batch", "lru")
        for k in ("conv1", "conv2"):
            ax[k] = ("layers", "cache_batch", None, "lru")
        if _hybrid_counts(cfg)[1]:
            ax["h_tail"] = ("layers", "cache_batch", "lru")
            ax["conv_tail"] = ("layers", "cache_batch", None, "lru")
    elif cfg.family == "audio":
        for k in ("k", "v", "xk", "xv"):
            ax[k] = ("layers", "cache_batch", "cache_seq", "cache_kv_heads", None)
    else:
        pairs = cfg.num_experts and cfg.moe_layer_freq == 2
        for k in ("k", "v"):
            ax[k] = (("layers", None) if pairs else ("layers",)) + (
                "cache_batch", "cache_seq", "cache_kv_heads", None)
    return ax


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    return {k: jnp.zeros(s, d) for k, (s, d) in
            cache_shapes(cfg, batch, max_seq, dtype).items()}


# ---------------------------------------------------------------------------
# Prefill — full prompt, returns last-token logits + populated cache
# ---------------------------------------------------------------------------


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            max_seq: int = 0, attn_impl: str = "auto",
            cache_dtype=jnp.bfloat16) -> Tuple[jax.Array, Dict]:
    tokens = batch["tokens"]
    B, S = tokens.shape
    max_seq = max_seq or S
    positions = jnp.arange(S)[None, :]

    if cfg.family == "audio":
        return _prefill_audio(params, cfg, batch, max_seq=max_seq,
                              attn_impl=attn_impl, cache_dtype=cache_dtype)

    h = embed_tokens(cfg, params, tokens)
    if cfg.family == "vlm":
        h = _merge_vision(cfg, h, batch["vision_embeds"])
    cache = init_cache(cfg, B, max_seq, cache_dtype)

    if cfg.family == "ssm":
        def body(carry, p_l):
            y, (ssm_s, conv_s) = ssm_block(cfg, p_l, carry)
            return y, (ssm_s, conv_s.astype(cache_dtype))
        h, (ssm_s, conv_s) = _ctl_scan(body, h, params["layers"])
        cache["ssm"], cache["conv"] = ssm_s, conv_s
    elif cfg.family == "hybrid":
        win = cache["k"].shape[2]

        def tri_body(carry, p_t):
            y, (h1, c1) = rglru_block(cfg, p_t["r1"], carry)
            y, (h2, c2) = rglru_block(cfg, p_t["r2"], y)
            y, (k, v) = attn_block(cfg, p_t["attn"], y, positions=positions,
                                   attn_impl=attn_impl, window=cfg.local_window)
            y = ffn_block(cfg, p_t["attn"], y)
            # keep only the trailing window in the ring cache (ring start = S % win)
            kw = _last_window(k, win).astype(cache_dtype)
            vw = _last_window(v, win).astype(cache_dtype)
            return y, (h1, h2, c1.astype(cache_dtype), c2.astype(cache_dtype), kw, vw)
        h, (h1, h2, c1, c2, kw, vw) = _ctl_scan(tri_body, h, params["blocks"])
        cache.update(h1=h1, h2=h2, conv1=c1, conv2=c2, k=kw, v=vw)
        if "tail" in params:
            def tail_body(carry, p_l):
                y, (hl, cl) = rglru_block(cfg, p_l, carry)
                return y, (hl, cl.astype(cache_dtype))
            h, (ht, ct) = _ctl_scan(tail_body, h, params["tail"])
            cache["h_tail"], cache["conv_tail"] = ht, ct
    elif "pairs" in params:
        def pair_body(carry, p_p):
            y = carry
            kvs = []
            for sub in ("dense", "moe"):
                y, (k, v) = attn_block(cfg, p_p[sub], y, positions=positions,
                                       attn_impl=attn_impl, window=cfg.local_window)
                y = ffn_block(cfg, p_p[sub], y)
                kvs.append((_pad_to(k, max_seq).astype(cache_dtype),
                            _pad_to(v, max_seq).astype(cache_dtype)))
            return y, (jnp.stack([kvs[0][0], kvs[1][0]]),
                       jnp.stack([kvs[0][1], kvs[1][1]]))
        h, (ks, vs) = _ctl_scan(pair_body, h, params["pairs"])
        cache["k"], cache["v"] = ks, vs
    else:
        def body(carry, p_l):
            y, (k, v) = attn_block(cfg, p_l, carry, positions=positions,
                                   attn_impl=attn_impl, window=cfg.local_window)
            y = ffn_block(cfg, p_l, y)
            kp = _pad_to(k, max_seq).astype(cache_dtype)
            vp = _pad_to(v, max_seq).astype(cache_dtype)
            return y, (kp, vp)
        h, (ks, vs) = _ctl_scan(body, h, params["layers"])
        cache["k"], cache["v"] = ks, vs

    cache["pos"] = jnp.asarray(S, jnp.int32)
    logits = lm_head(cfg, params, h[:, -1:, :])
    return logits[:, 0], cache


def _pad_to(k: jax.Array, max_seq: int) -> jax.Array:
    S = k.shape[1]
    if S == max_seq:
        return k
    pad = [(0, 0)] * k.ndim
    pad[1] = (0, max_seq - S)
    return jnp.pad(k, pad)


def _last_window(k: jax.Array, win: int) -> jax.Array:
    """Trailing `win` positions arranged as a ring buffer with slot = pos % win."""
    S = k.shape[1]
    if S <= win:
        return _pad_to(k, win)
    tail = k[:, S - win:]                                  # abs positions S-win..S-1
    # slot of absolute position p is p % win; roll so tail[i] lands at slot
    shift = (S - win) % win
    return jnp.roll(tail, shift, axis=1)


def _prefill_audio(params, cfg, batch, *, max_seq, attn_impl, cache_dtype):
    """Whisper: 'prefill' = run the encoder + project cross K/V; decoder self-cache
    starts empty (generation starts from BOS tokens in batch['tokens'])."""
    frames = batch["frames"]
    B, Tenc = frames.shape[:2]
    h = frames + L.sinusoidal_positions(Tenc, cfg.d_model)[None].astype(frames.dtype)
    enc_pos = jnp.arange(Tenc)[None, :]

    def enc_body(carry, p_l):
        y, _ = attn_block(cfg, p_l, carry, positions=enc_pos, attn_impl=attn_impl,
                          use_rope=False, causal=False)
        y = ffn_block(cfg, p_l, y)
        return y, None
    h, _ = _ctl_scan(enc_body, h, params["enc_layers"])
    enc_out = L.layer_norm(h, params["enc_final_norm"], params["enc_final_norm_b"],
                           cfg.norm_eps)

    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    cache = init_cache(cfg, B, max_seq, cache_dtype)

    def cross_kv(carry, p_l):
        xk = jnp.einsum("btd,dq->btq", enc_out, p_l["wk_x"])
        xv = jnp.einsum("btd,dq->btq", enc_out, p_l["wv_x"])
        return carry, (xk.reshape(B, Tenc, K, hd).astype(cache_dtype),
                       xv.reshape(B, Tenc, K, hd).astype(cache_dtype))
    _, (xk, xv) = _ctl_scan(cross_kv, 0, params["layers"])
    cache["xk"], cache["xv"] = xk, xv
    cache["pos"] = jnp.asarray(0, jnp.int32)

    # first decoder token logits from BOS
    logits = jnp.zeros((B, cfg.vocab_size), frames.dtype)
    return logits, cache


# ---------------------------------------------------------------------------
# Decode step — one new token against the cache
# ---------------------------------------------------------------------------


def _decode_attn_sublayer(cfg: ModelConfig, p_l: Params, y: jax.Array,
                          k_l, v_l, pos, *, attn_impl: str,
                          xk_l=None, xv_l=None):
    """One decode attention layer (self-attn + optional cross-attn + FFN).
    Returns (y, k_l, v_l) with the cache slice updated at `pos`."""
    B = y.shape[0]
    K, hd, H = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_heads
    if cfg.family == "audio":
        x = L.layer_norm(y, p_l["ln1"], p_l["ln1_b"], cfg.norm_eps)
    else:
        x = L.rms_norm(y, p_l["ln1"], cfg.norm_eps)
    q, k, v = _project_qkv(cfg, p_l, x)
    if cfg.family != "audio":
        rp = pos[None, None] + jnp.zeros((1, 1), jnp.int32)
        q = L.apply_rope(q, rp, cfg.rope_theta)
        k = L.apply_rope(k, rp, cfg.rope_theta)
    k_l = lax.dynamic_update_slice_in_dim(k_l, k.astype(k_l.dtype), pos, axis=1)
    v_l = lax.dynamic_update_slice_in_dim(v_l, v.astype(v_l.dtype), pos, axis=1)
    k_l = logical(k_l, "cache_batch", "cache_seq", "cache_kv_heads", None)
    v_l = logical(v_l, "cache_batch", "cache_seq", "cache_kv_heads", None)
    o = L.attention(q, k_l, v_l, impl=attn_impl, causal=True, q_offset=pos)
    y = y + jnp.einsum("bsq,qd->bsd", o.reshape(B, 1, -1), p_l["wo"])
    if xk_l is not None:
        x = L.layer_norm(y, p_l["ln_x"], p_l["ln_x_b"], cfg.norm_eps)
        qx = jnp.einsum("bsd,dq->bsq", x, p_l["wq_x"]).reshape(B, 1, H, hd)
        ox = L.attention(qx, xk_l, xv_l, impl=attn_impl, causal=False)
        y = y + jnp.einsum("bsq,qd->bsd", ox.reshape(B, 1, -1), p_l["wo_x"])
    y = ffn_block(cfg, p_l, y)
    return y, k_l, v_l


def decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                cache: Dict, *, attn_impl: str = "naive") -> Tuple[jax.Array, Dict]:
    """tokens: (B,) int32 — the token generated at position cache['pos'].
    Returns (logits (B, V), updated cache)."""
    pos = cache["pos"]
    h = embed_tokens(cfg, params, tokens[:, None])         # (B, 1, D)

    if cfg.family == "ssm":
        return _decode_ssm(params, cfg, h, cache)
    if cfg.family == "hybrid":
        return _decode_hybrid(params, cfg, h, cache, attn_impl=attn_impl)
    if cfg.family == "audio":
        h = h + L.sinusoidal_positions(1, cfg.d_model)[None].astype(h.dtype)

    if "pairs" in params:
        def body(carry, xs):
            y = carry
            p_p, k_l, v_l = xs                             # k_l: (2, B, S, K, hd)
            y, k0, v0 = _decode_attn_sublayer(cfg, p_p["dense"], y, k_l[0], v_l[0],
                                              pos, attn_impl=attn_impl)
            y, k1, v1 = _decode_attn_sublayer(cfg, p_p["moe"], y, k_l[1], v_l[1],
                                              pos, attn_impl=attn_impl)
            return y, (jnp.stack([k0, k1]), jnp.stack([v0, v1]))
        h, (k_new, v_new) = _ctl_scan(body, h, (params["pairs"], cache["k"], cache["v"]))
    elif cfg.family == "audio":
        def body(carry, xs):
            p_l, k_l, v_l, xk_l, xv_l = xs
            y, k_l, v_l = _decode_attn_sublayer(cfg, p_l, carry, k_l, v_l, pos,
                                                attn_impl=attn_impl,
                                                xk_l=xk_l, xv_l=xv_l)
            return y, (k_l, v_l)
        h, (k_new, v_new) = _ctl_scan(
            body, h, (params["layers"], cache["k"], cache["v"],
                      cache["xk"], cache["xv"]))
    else:
        def body(carry, xs):
            p_l, k_l, v_l = xs
            y, k_l, v_l = _decode_attn_sublayer(cfg, p_l, carry, k_l, v_l, pos,
                                                attn_impl=attn_impl)
            return y, (k_l, v_l)
        h, (k_new, v_new) = _ctl_scan(body, h, (params["layers"], cache["k"], cache["v"]))

    cache = dict(cache, k=k_new, v=v_new, pos=pos + 1)
    logits = lm_head(cfg, params, h)
    return logits[:, 0], cache


def supports_ragged_decode(cfg: ModelConfig) -> bool:
    """Families whose decode cache is a dense per-layer K/V stack with a
    single position pointer — the shapes the batched paged decode runtime
    (`decode_step_ragged` + PagedKVCache) handles. Recurrent-state families
    (ssm/hybrid), encoder-decoder audio, and the interleaved MoE pair layout
    stay on the single-stream `decode_step` path."""
    if cfg.family in ("ssm", "hybrid", "audio"):
        return False
    if cfg.num_experts and cfg.moe_layer_freq == 2:
        return False
    return True


def _ragged_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             kv_lens: jax.Array, attn_impl: str) -> jax.Array:
    """q: (B, H, hd); k/v: (B, T, K, hd); kv_lens: (B,) valid key counts.
    Row b attends to keys [0, kv_lens[b]) of its own KV view."""
    if attn_impl in ("pallas", "pallas_interpret"):
        # runtime import: kernels.ops imports models.layers; importing it at
        # module scope from here would tie the model to the kernel package
        from repro.kernels.ops import decode_attention
        return decode_attention(q, k, v, kv_lens, impl=attn_impl)
    out = L.naive_attention(q[:, None], k, v, causal=False, kv_len=kv_lens)
    return out[:, 0]


def decode_step_ragged(params: Params, cfg: ModelConfig, tokens: jax.Array,
                       k_gathered: jax.Array, v_gathered: jax.Array,
                       kv_lens: jax.Array, *, attn_impl: str = "naive"):
    """One continuous-batching decode step over B resident streams.

    tokens: (B,) int32 — each stream's current token; k_gathered/v_gathered:
    (L, B, T, K, hd) dense per-stream KV views (PagedKVCache.gather_batch),
    padded to a common T; kv_lens: (B,) int32 — stream b's context length,
    which is also the position its new K/V belongs at (padding slots carry
    kv_len 0 and their outputs are discarded by the caller).

    Returns (logits (B, V), k_new (L, B, K, hd), v_new (L, B, K, hd)): the
    new per-layer K/V are handed back for the caller to scatter into the
    paged pool (PagedKVCache.write_tokens) — the whole step is ONE jitted
    program per (B, T) shape bucket, one batched cache write per token,
    instead of per-stream O(pool) functional updates.
    """
    if not supports_ragged_decode(cfg):
        raise NotImplementedError(
            f"batched ragged decode unsupported for family={cfg.family!r} "
            f"(moe_layer_freq={cfg.moe_layer_freq}); use decode_step")
    B = tokens.shape[0]
    pos = kv_lens.astype(jnp.int32)
    rows = jnp.arange(B)
    h = embed_tokens(cfg, params, tokens[:, None])          # (B, 1, D)

    def body(carry, xs):
        p_l, k_l, v_l = xs                                  # k_l: (B,T,K,hd)
        y = carry
        x = L.rms_norm(y, p_l["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(cfg, p_l, x)                 # (B, 1, ·, hd)
        rp = pos[:, None]                                   # (B, 1) positions
        q = L.apply_rope(q, rp, cfg.rope_theta)
        k = L.apply_rope(k, rp, cfg.rope_theta)
        # batched scatter of the new token into the gathered views so
        # attention sees prefix + self; the pool write happens in the caller
        k_full = k_l.at[rows, pos].set(k[:, 0].astype(k_l.dtype))
        v_full = v_l.at[rows, pos].set(v[:, 0].astype(v_l.dtype))
        o = _ragged_decode_attention(q[:, 0], k_full, v_full, pos + 1,
                                     attn_impl)             # (B, H, hd)
        y = y + jnp.einsum("bsq,qd->bsd", o.reshape(B, 1, -1), p_l["wo"])
        y = ffn_block(cfg, p_l, y)
        return y, (k[:, 0], v[:, 0])

    h, (k_new, v_new) = _ctl_scan(
        body, h, (params["layers"], k_gathered, v_gathered))
    logits = lm_head(cfg, params, h)
    return logits[:, 0], k_new, v_new


def _ragged_verify_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             kv_lens: jax.Array, attn_impl: str) -> jax.Array:
    """q: (B, S, H, hd) — S consecutive query positions starting at
    kv_lens[b] per row; k/v: (B, T, K, hd) with the S draft K/V already
    scattered in. Per-row causal masking: query s of row b attends keys
    [0, kv_lens[b] + s]."""
    if attn_impl in ("pallas", "pallas_interpret"):
        from repro.kernels.ops import verify_attention
        return verify_attention(q, k, v, kv_lens, impl=attn_impl)
    return L.naive_attention(q, k, v, causal=True, q_offset=kv_lens)


def decode_verify_ragged(params: Params, cfg: ModelConfig, tokens: jax.Array,
                         k_gathered: jax.Array, v_gathered: jax.Array,
                         kv_lens: jax.Array, *, attn_impl: str = "naive"):
    """Speculative-verify sibling of `decode_step_ragged`: score S = k + 1
    consecutive positions per stream in ONE jitted step.

    tokens: (B, S) int32 — row b holds [current token, draft_0 .. draft_{k-1}]
    (short drafts padded arbitrarily; padded columns simply produce logits the
    caller never accepts). kv_lens: (B,) — the committed context length of
    row b, i.e. the position tokens[b, 0] is written at. Returns
    (logits (B, S, V), k_new (L, B, S, K, hd), v_new (L, B, S, K, hd)).

    Greedy acceptance contract: because column s attends exactly the keys a
    plain step at position kv_lens[b] + s would see (committed prefix + the
    s earlier draft keys, masked identically), logits[:, s] is bit-equal to
    what `decode_step_ragged` would produce after committing those s tokens
    — so accepting the longest draft prefix matching argmax(logits) yields
    output bit-identical to plain greedy decoding.
    """
    if not supports_ragged_decode(cfg):
        raise NotImplementedError(
            f"speculative verify unsupported for family={cfg.family!r} "
            f"(moe_layer_freq={cfg.moe_layer_freq}); use decode_step")
    B, S = tokens.shape
    pos = kv_lens.astype(jnp.int32)
    rows = jnp.arange(B)
    h = embed_tokens(cfg, params, tokens)                   # (B, S, D)

    def body(carry, xs):
        p_l, k_l, v_l = xs                                  # k_l: (B,T,K,hd)
        y = carry
        x = L.rms_norm(y, p_l["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(cfg, p_l, x)                 # (B, S, ·, hd)
        rp = pos[:, None] + jnp.arange(S)[None, :]          # (B, S) positions
        q = L.apply_rope(q, rp, cfg.rope_theta)
        k = L.apply_rope(k, rp, cfg.rope_theta)
        # scatter the whole draft span into the gathered views; the causal
        # per-row mask in the attention below keeps column s blind to the
        # later draft keys, so rejected positions never leak into accepted
        # logits. The pool write (and the commit/rollback decision) happens
        # in the caller.
        k_full = k_l.at[rows[:, None], rp].set(k.astype(k_l.dtype))
        v_full = v_l.at[rows[:, None], rp].set(v.astype(v_l.dtype))
        o = _ragged_verify_attention(q, k_full, v_full, pos, attn_impl)
        y = y + jnp.einsum("bsq,qd->bsd", o.reshape(B, S, -1), p_l["wo"])
        y = ffn_block(cfg, p_l, y)
        return y, (k, v)

    h, (k_new, v_new) = _ctl_scan(
        body, h, (params["layers"], k_gathered, v_gathered))
    logits = lm_head(cfg, params, h)
    return logits, k_new, v_new


def _decode_ssm(params, cfg, h, cache):
    B = h.shape[0]
    din, N, nh, W = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv_width
    P_ = cfg.ssm_head_dim

    def body(carry, xs):
        y = carry
        p_l, ssm_s, conv_s = xs
        x = L.rms_norm(y, p_l["ln"], cfg.norm_eps)
        proj = jnp.einsum("bsd,dp->bsp", x, p_l["in_proj"])
        z, xBC, dt = jnp.split(proj, [din, 2 * din + 2 * N], axis=-1)
        # rolling conv state: append, convolve last position
        window = jnp.concatenate([conv_s.astype(xBC.dtype), xBC], axis=1)  # (B,W,C)
        conv_out = jnp.einsum("bwc,cw->bc", window, p_l["conv_w"]) + p_l["conv_b"]
        conv_out = jax.nn.silu(conv_out)
        xs_, Bm, Cm = jnp.split(conv_out, [din, din + N], axis=-1)
        dt_sp = jax.nn.softplus(dt[:, 0] + p_l["dt_bias"])
        A = -jnp.exp(p_l["A_log"].astype(jnp.float32))
        yv, ssm_s = L.ssd_step(xs_.reshape(B, nh, P_), dt_sp, A, Bm, Cm, ssm_s)
        yv = yv + xs_.reshape(B, nh, P_) * p_l["Dp"][None, :, None]
        yv = yv.reshape(B, 1, din)
        yv = L.rms_norm(yv * jax.nn.silu(z), p_l["norm_w"], cfg.norm_eps)
        y = y + jnp.einsum("bsc,cd->bsd", yv, p_l["out_proj"])
        return y, (ssm_s, window[:, 1:].astype(conv_s.dtype))

    h, (ssm_new, conv_new) = _ctl_scan(
        body, h, (params["layers"], cache["ssm"], cache["conv"]))
    cache = dict(cache, ssm=ssm_new, conv=conv_new, pos=cache["pos"] + 1)
    logits = lm_head(cfg, params, h)
    return logits[:, 0], cache


def _decode_hybrid(params, cfg, h, cache, *, attn_impl="naive"):
    B = h.shape[0]
    pos = cache["pos"]
    win = cache["k"].shape[2]

    def rglru_step_block(p_l, y, h_s, conv_s):
        x = L.rms_norm(y, p_l["ln1"], cfg.norm_eps)
        gate = jax.nn.gelu(jnp.einsum("bsd,dc->bsc", x, p_l["w_gate"]))
        xb = jnp.einsum("bsd,dc->bsc", x, p_l["w_x"])
        window = jnp.concatenate([conv_s.astype(xb.dtype), xb], axis=1)
        conv_out = (jnp.einsum("bwc,cw->bc", window, p_l["conv_w"]) + p_l["conv_b"])
        yv, h_s = L.rglru_step(conv_out, p_l["a_param"], p_l["w_rg"], p_l["w_ig"], h_s)
        y = y + jnp.einsum("bsc,cd->bsd", yv[:, None] * gate, p_l["w_y"])
        y = ffn_block(cfg, p_l, y)
        return y, h_s, window[:, 1:].astype(conv_s.dtype)

    def tri_body(carry, xs):
        y = carry
        p_t, k_l, v_l, h1, h2, c1, c2 = xs
        y, h1, c1 = rglru_step_block(p_t["r1"], y, h1, c1)
        y, h2, c2 = rglru_step_block(p_t["r2"], y, h2, c2)
        # local attention over ring buffer
        p_l = p_t["attn"]
        x = L.rms_norm(y, p_l["ln1"], cfg.norm_eps)
        q, k, v = _project_qkv(cfg, p_l, x)
        rp = pos[None, None] + jnp.zeros((1, 1), jnp.int32)
        q = L.apply_rope(q, rp, cfg.rope_theta)
        k = L.apply_rope(k, rp, cfg.rope_theta)
        slot = pos % win
        k_l = lax.dynamic_update_slice_in_dim(k_l, k.astype(k_l.dtype), slot, axis=1)
        v_l = lax.dynamic_update_slice_in_dim(v_l, v.astype(v_l.dtype), slot, axis=1)
        # absolute position of each ring slot j: pos - ((pos - j) mod win)
        j = jnp.arange(win)
        k_pos = pos - ((pos - j) % win)
        o = L.naive_attention(q, k_l, v_l, causal=True, q_offset=pos,
                              local_window=cfg.local_window, k_positions=k_pos)
        y = y + jnp.einsum("bsq,qd->bsd", o.reshape(B, 1, -1), p_l["wo"])
        y = ffn_block(cfg, p_l, y)
        return y, (k_l, v_l, h1, h2, c1, c2)

    xs = (params["blocks"], cache["k"], cache["v"], cache["h1"], cache["h2"],
          cache["conv1"], cache["conv2"])
    h, (k_n, v_n, h1_n, h2_n, c1_n, c2_n) = _ctl_scan(tri_body, h, xs)
    cache = dict(cache, k=k_n, v=v_n, h1=h1_n, h2=h2_n, conv1=c1_n, conv2=c2_n)
    if "tail" in params:
        def tail_body(carry, xs_):
            y = carry
            p_l, h_s, c_s = xs_
            y, h_s, c_s = rglru_step_block(p_l, y, h_s, c_s)
            return y, (h_s, c_s)
        h, (ht_n, ct_n) = _ctl_scan(
            tail_body, h, (params["tail"], cache["h_tail"], cache["conv_tail"]))
        cache = dict(cache, h_tail=ht_n, conv_tail=ct_n)
    cache = dict(cache, pos=pos + 1)
    logits = lm_head(cfg, params, h)
    return logits[:, 0], cache
