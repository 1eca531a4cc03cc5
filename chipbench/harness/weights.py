"""Random weights for a dense Qwen2-family model, made on the device from the
seed in one jitted call, in the type they are served in.

The layout is the serving program's parameter pytree for a dense stack
(stacked layers, gate and up on an explicit axis of size 2, norm scales
stored as an offset from 1, tied embeddings); `run.py` checks it against
the program's own shapes before serving. The values are the benchmark's:
the reference reads the same arrays, and nothing here comes from the
program.

Distribution: linear weights and the embedding N(0, initializer_range)
(0.02 in Qwen2's config); q/k/v biases N(0, 0.02) and norm scales 1 + N(0,
0.1), so that a dropped bias or norm scale shows in the logits. The query
and key projections are drawn wider, N(0, sqrt(QK_LOGIT_STD / hidden)), so
that attention logits spread by about QK_LOGIT_STD as in a trained model:
at 0.02 attention is nearly uniform, each next token is a function of the
current one alone, and greedy decoding repeats one token whatever the
cache holds, which would leave the decode path's attention unchecked.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from harness.flops import dims

NORM_STD = 0.1
BIAS_STD = 0.02
QK_LOGIT_STD = 3.0


def shapes(cfg: dict) -> Dict[str, object]:
    m = dims(cfg)
    d, L, H, K, hd, F, V = (m[k] for k in ("d", "L", "H", "K", "hd", "F", "V"))
    layer = {
        "ln1": (d,), "wq": (d, H * hd), "wk": (d, K * hd), "wv": (d, K * hd),
        "wo": (H * hd, d), "bq": (H * hd,), "bk": (K * hd,), "bv": (K * hd,),
        "ln2": (d,), "wi": (d, 2, F), "wd": (F, d),
    }
    return {"embed": (V, d), "final_norm": (d,),
            "layers": {k: (L,) + s for k, s in layer.items()}}


def _std(name: str, cfg: dict) -> Tuple[float, float]:
    """(mean, std) of the stored value of a leaf."""
    if name.startswith("ln") or name == "final_norm":
        return 0.0, NORM_STD
    if name.startswith("b"):
        return 0.0, BIAS_STD
    if name in ("wq", "wk"):
        return 0.0, (QK_LOGIT_STD / int(cfg["hidden_size"])) ** 0.5
    return 0.0, float(cfg["initializer_range"])


def make(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """The params pytree on the default device, from `seed`."""
    tree = shapes(cfg)
    flat = []

    def walk(prefix, node):
        for k in sorted(node):
            if isinstance(node[k], dict):
                walk(prefix + (k,), node[k])
            else:
                flat.append((prefix + (k,), node[k]))
    walk((), tree)

    def init(key):
        keys = jax.random.split(key, len(flat))
        out: Dict = {}
        for (path, shape), k in zip(flat, keys):
            mean, std = _std(path[-1], cfg)

            def draw(k, shape=shape, mean=mean, std=std):
                x = mean + std * jax.random.normal(k, shape, jnp.float32)
                return x.astype(dtype)
            if path[0] == "layers":
                # one layer at a time: the float32 draw of a stacked leaf
                # would otherwise sit whole in device memory
                x = jax.lax.map(lambda kk: draw(kk, shape[1:]),
                                jax.random.split(k, shape[0]))
            else:
                x = draw(k)
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = x
        return out

    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(init)(key)
