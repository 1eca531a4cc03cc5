"""The Qwen2 family: a dense decoder stack (Qwen2 report, arXiv:2407.10671).

Pre-norm RMSNorm, q/k/v projections with bias, rotary embedding on
half-split channels, grouped-query causal softmax attention over the whole
context scaled by 1/sqrt(head_dim), SwiGLU MLP, final RMSNorm, output head
tied to the embedding. A configuration with an untied head or a sliding
window is refused: this module models neither.

What the harness asks of a family:

    program_config(cfg)    the program's ModelConfig, sizes from the file
    shapes(cfg)            the weight pytree's shapes
    distribution(path, cfg)  (mean, std) of the stored value of a leaf
    logits_at(params, cfg, tokens, positions, mode)  the float32 reference
                           (mode "f32") or the fp8 control (mode "fp8")
    token_flops(cfg), head_flops(cfg), attention(cfg)  the counts of
                           `harness.flops`
"""
from __future__ import annotations

import dataclasses
import importlib
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference as ref
from harness.flops import Attn

# Weights: linear weights and the embedding N(0, initializer_range) (0.02
# in Qwen2's config); q/k/v biases N(0, 0.02) and norm scales 1 + N(0,
# 0.1), so that a dropped bias or norm scale shows in the logits. The query
# and key projections are drawn wider, N(0, sqrt(QK_LOGIT_STD / hidden)),
# so that attention logits spread by about QK_LOGIT_STD as in a trained
# model: at 0.02 attention is nearly uniform, each next token is a function
# of the current one alone, and greedy decoding repeats one token whatever
# the cache holds, which would leave the decode path's attention unchecked.
NORM_STD = 0.1
BIAS_STD = 0.02
QK_LOGIT_STD = 3.0


def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    return {"d": d, "L": int(cfg["num_hidden_layers"]), "H": heads,
            "K": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // heads),
            "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"])}


def program_config(cfg: dict):
    """The program's ModelConfig: the module the file names, with every
    size taken from the file."""
    if not cfg["tie_word_embeddings"] or cfg.get("use_sliding_window"):
        raise ValueError(f"{cfg['name']}: the qwen2 family models a tied "
                         f"head over full attention only")
    mod = importlib.import_module(f"repro.configs.{cfg['module']}")
    pc = dataclasses.replace(
        mod.CONFIG,
        num_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]),
        d_ff=int(cfg["intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        qkv_bias=True)
    if pc.family != "dense" or pc.num_experts or pc.local_window:
        raise ValueError(f"{cfg['module']} is not a dense full-attention "
                         f"stack; the qwen2 family models only those")
    return pc


def shapes(cfg: dict) -> Dict[str, object]:
    """The program's parameter pytree for this stack: layers stacked on a
    leading axis, gate and up on an explicit axis of size 2, norm scales
    stored as an offset from 1, tied embeddings."""
    m = dims(cfg)
    d, L, H, K, hd, F, V = (m[k] for k in ("d", "L", "H", "K", "hd", "F", "V"))
    layer = {
        "ln1": (d,), "wq": (d, H * hd), "wk": (d, K * hd), "wv": (d, K * hd),
        "wo": (H * hd, d), "bq": (H * hd,), "bk": (K * hd,), "bv": (K * hd,),
        "ln2": (d,), "wi": (d, 2, F), "wd": (F, d),
    }
    return {"embed": (V, d), "final_norm": (d,),
            "layers": {k: (L,) + s for k, s in layer.items()}}


def distribution(path: Tuple[str, ...], cfg: dict) -> Tuple[float, float]:
    """(mean, std) of the stored value of the leaf at `path`."""
    name = path[-1]
    if name.startswith("ln") or name == "final_norm":
        return 0.0, NORM_STD
    if name.startswith("b"):
        return 0.0, BIAS_STD
    if name in ("wq", "wk"):
        return 0.0, (QK_LOGIT_STD / int(cfg["hidden_size"])) ** 0.5
    return 0.0, float(cfg["initializer_range"])


@partial(jax.jit, static_argnames=("H", "K", "hd", "eps", "theta", "mode"))
def _layer(layers, l, h, *, H, K, hd, eps, theta, mode):
    p = jax.tree.map(lambda a: a[l], layers)
    S = h.shape[0]
    pos = jnp.arange(S)
    x = ref.rms(h, p["ln1"], eps)
    q = ref.mm("sd,dq->sq", x, p["wq"], mode) + p["bq"].astype(jnp.float32)
    k = ref.mm("sd,dq->sq", x, p["wk"], mode) + p["bk"].astype(jnp.float32)
    v = ref.mm("sd,dq->sq", x, p["wv"], mode) + p["bv"].astype(jnp.float32)
    q = ref.rope(q.reshape(S, H, hd), pos, theta)
    k = ref.rope(k.reshape(S, K, hd), pos, theta)
    o = ref.causal_attention(q, k, v.reshape(S, K, hd), mode)
    h = h + ref.mm("sq,qd->sd", o, p["wo"], mode)
    x = ref.rms(h, p["ln2"], eps)
    gate = ref.mm("sd,df->sf", x, p["wi"][:, 0], mode)
    up = ref.mm("sd,df->sf", x, p["wi"][:, 1], mode)
    return h + ref.mm("sf,fd->sd", jax.nn.silu(gate) * up, p["wd"], mode)


@partial(jax.jit, static_argnames=("mode",))
def _embed(embed, tokens, *, mode):
    return embed[tokens].astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps", "mode"))
def _head(final_norm, embed, h, positions, *, eps, mode):
    x = ref.rms(h[positions], final_norm, eps)
    return ref.mm("pd,vd->pv", x, embed, mode)


def logits_at(params, cfg: dict, tokens: np.ndarray, positions: np.ndarray,
              mode: str = "f32") -> np.ndarray:
    """Logits (len(positions), vocab) of one sequence at the given
    positions, each predicting the token after it."""
    m = dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    toks, pos = ref.pad(tokens, positions)
    h = _embed(params["embed"], toks, mode=mode)
    for l in range(m["L"]):
        h = _layer(params["layers"], l, h, H=m["H"], K=m["K"], hd=m["hd"],
                   eps=eps, theta=theta, mode=mode)
    out = _head(params["final_norm"], params["embed"], h, pos, eps=eps,
                mode=mode)
    return np.asarray(out)[:len(positions)]


def layer_matmul_flops(cfg: dict) -> int:
    """Multiply-adds x 2 of one layer's projections for one token: q, k, v,
    o and the gated MLP (gate, up, down)."""
    m = dims(cfg)
    d, H, K, hd, F = m["d"], m["H"], m["K"], m["hd"], m["F"]
    return 2 * (d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * F)


def token_flops(cfg: dict) -> int:
    return dims(cfg)["L"] * layer_matmul_flops(cfg)


def head_flops(cfg: dict) -> int:
    m = dims(cfg)
    return 2 * m["d"] * m["V"]


def attention(cfg: dict) -> List[Attn]:
    """Every layer attends over its whole causal context."""
    m = dims(cfg)
    return [Attn(m["H"], m["K"], m["hd"])] * m["L"]
