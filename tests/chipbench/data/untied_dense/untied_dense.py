"""A dense decoder stack with no q/k/v bias and an untied output head:
pre-norm RMSNorm, rotary embedding on half-split channels, grouped-query
causal softmax attention over the whole context, SwiGLU MLP, final RMSNorm,
and an output projection of its own (`unembed`, hidden x vocab).

A family module kept beside the tests: they copy it into a copy of the
benchmark's `families/`, with a configuration that names it, to show that
a family brought as new files is served, checked and counted with no
harness file edited.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference as ref
from harness.flops import Attn

NORM_STD = 0.1
QK_LOGIT_STD = 3.0     # attention logits spread as in a trained model


def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    return {"d": d, "L": int(cfg["num_hidden_layers"]), "H": heads,
            "K": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // heads),
            "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"])}


def program_config(cfg: dict):
    from repro.configs.base import ModelConfig
    if cfg["tie_word_embeddings"] or cfg.get("attention_bias"):
        raise ValueError(f"{cfg['name']}: the untied_dense family models an "
                         f"untied head and no q/k/v bias only")
    m = dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=m["L"],
        d_model=m["d"], num_heads=m["H"], num_kv_heads=m["K"],
        head_dim=m["hd"], d_ff=m["F"], vocab_size=m["V"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), tie_embeddings=False,
        qkv_bias=False)


def shapes(cfg: dict) -> Dict[str, object]:
    m = dims(cfg)
    d, L, H, K, hd, F, V = (m[k] for k in ("d", "L", "H", "K", "hd", "F", "V"))
    layer = {"ln1": (d,), "wq": (d, H * hd), "wk": (d, K * hd),
             "wv": (d, K * hd), "wo": (H * hd, d), "ln2": (d,),
             "wi": (d, 2, F), "wd": (F, d)}
    return {"embed": (V, d), "unembed": (d, V), "final_norm": (d,),
            "layers": {k: (L,) + s for k, s in layer.items()}}


def distribution(path: Tuple[str, ...], cfg: dict) -> Tuple[float, float]:
    name = path[-1]
    if name.startswith("ln") or name == "final_norm":
        return 0.0, NORM_STD
    if name in ("wq", "wk"):
        return 0.0, (QK_LOGIT_STD / int(cfg["hidden_size"])) ** 0.5
    return 0.0, float(cfg["initializer_range"])


@partial(jax.jit, static_argnames=("H", "K", "hd", "eps", "theta", "mode"))
def _layer(layers, l, h, *, H, K, hd, eps, theta, mode):
    p = jax.tree.map(lambda a: a[l], layers)
    S = h.shape[0]
    pos = jnp.arange(S)
    x = ref.rms(h, p["ln1"], eps)
    q = ref.rope(ref.mm("sd,dq->sq", x, p["wq"], mode).reshape(S, H, hd),
                 pos, theta)
    k = ref.rope(ref.mm("sd,dq->sq", x, p["wk"], mode).reshape(S, K, hd),
                 pos, theta)
    v = ref.mm("sd,dq->sq", x, p["wv"], mode).reshape(S, K, hd)
    h = h + ref.mm("sq,qd->sd", ref.causal_attention(q, k, v, mode),
                   p["wo"], mode)
    x = ref.rms(h, p["ln2"], eps)
    gate = ref.mm("sd,df->sf", x, p["wi"][:, 0], mode)
    up = ref.mm("sd,df->sf", x, p["wi"][:, 1], mode)
    return h + ref.mm("sf,fd->sd", jax.nn.silu(gate) * up, p["wd"], mode)


@partial(jax.jit, static_argnames=("eps", "mode"))
def _head(final_norm, unembed, h, positions, *, eps, mode):
    x = ref.rms(h[positions], final_norm, eps)
    return ref.mm("pd,dv->pv", x, unembed, mode)


def logits_at(params, cfg: dict, tokens: np.ndarray, positions: np.ndarray,
              mode: str = "f32") -> np.ndarray:
    m = dims(cfg)
    eps = float(cfg["rms_norm_eps"])
    toks, pos = ref.pad(tokens, positions)
    h = params["embed"][toks].astype(jnp.float32)
    for l in range(m["L"]):
        h = _layer(params["layers"], l, h, H=m["H"], K=m["K"], hd=m["hd"],
                   eps=eps, theta=float(cfg["rope_theta"]), mode=mode)
    out = _head(params["final_norm"], params["unembed"], h, pos, eps=eps,
                mode=mode)
    return np.asarray(out)[:len(positions)]


def token_flops(cfg: dict) -> int:
    m = dims(cfg)
    d, H, K, hd, F = m["d"], m["H"], m["K"], m["hd"], m["F"]
    return m["L"] * 2 * (2 * d * H * hd + 2 * d * K * hd + 3 * d * F)


def head_flops(cfg: dict) -> int:
    m = dims(cfg)
    return 2 * m["d"] * m["V"]


def attention(cfg: dict) -> List[Attn]:
    m = dims(cfg)
    return [Attn(m["H"], m["K"], m["hd"])] * m["L"]
