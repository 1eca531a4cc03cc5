"""Plain reference forward of a dense Qwen2-family model, in float32.

Straight `jax.numpy` from the published description (Qwen2: pre-norm
RMSNorm, q/k/v projections with bias, rotary embedding on half-split
channels, grouped-query causal softmax attention scaled by 1/sqrt(head_dim),
SwiGLU MLP, final RMSNorm, tied output embedding), over the weight pytree of
`harness.weights` (norm scales stored as an offset from 1). No kernel, cache
or batching: one sequence, all its positions, every matmul at HIGHEST
precision so a TPU does not round float32 operands to bfloat16. It imports
nothing of the program.

It runs layer by layer, one jitted layer program per padded length, and
attention in blocks of queries, so that a 16k-token sequence fits beside the
weights. Right padding does not change earlier positions under the causal
mask.

`mode="fp8"` is the control: every matmul operand (weights, activations,
queries, keys, probabilities, values) rounded to float8_e4m3fn under a
per-tensor scale, then multiplied in float32 — the precision step below the
bfloat16 the configuration serves in.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from harness.flops import dims

HIGHEST = jax.lax.Precision.HIGHEST
PAD_TO = 2048          # sequence length bucket (compiles one program each)
POS_PAD = 128          # position-count bucket of the output head
Q_BLOCK = 512          # queries per attention block
FP8_MAX = 448.0        # largest finite float8_e4m3fn


def _q(x, mode):
    """An operand as the mode computes it: float32, or rounded to fp8 under
    a per-tensor absmax scale."""
    x = x.astype(jnp.float32)
    if mode == "f32":
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, mode):
    return jnp.einsum(eq, _q(a, mode), _q(b, mode), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
    """x (S, n, hd): rotate the two halves of each head by pos * freq."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnames=("m", "eps", "theta", "mode"))
def _layer(layers, l, h, *, m, eps, theta, mode):
    p = jax.tree.map(lambda a: a[l], layers)
    S = h.shape[0]
    H, K, hd = m["H"], m["K"], m["hd"]
    pos = jnp.arange(S)
    x = _rms(h, p["ln1"], eps)
    q = _mm("sd,dq->sq", x, p["wq"], mode) + p["bq"].astype(jnp.float32)
    k = _mm("sd,dq->sq", x, p["wk"], mode) + p["bk"].astype(jnp.float32)
    v = _mm("sd,dq->sq", x, p["wv"], mode) + p["bv"].astype(jnp.float32)
    q = _rope(q.reshape(S, H, hd), pos, theta)
    k = _rope(k.reshape(S, K, hd), pos, theta)
    v = v.reshape(S, K, hd)
    rep = H // K                      # query head j reads kv head j // rep
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = _mm("qhd,khd->hqk", qb, k, mode) / jnp.sqrt(jnp.float32(hd))
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return _mm("hqk,khd->qhd", pr, v, mode)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK))
    o = o.reshape(S, H * hd)
    h = h + _mm("sq,qd->sd", o, p["wo"], mode)
    x = _rms(h, p["ln2"], eps)
    gate = _mm("sd,df->sf", x, p["wi"][:, 0], mode)
    up = _mm("sd,df->sf", x, p["wi"][:, 1], mode)
    return h + _mm("sf,fd->sd", jax.nn.silu(gate) * up, p["wd"], mode)


@partial(jax.jit, static_argnames=("mode",))
def _embed(embed, tokens, *, mode):
    return embed[tokens].astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps", "mode"))
def _head(final_norm, embed, h, positions, *, eps, mode):
    x = _rms(h[positions], final_norm, eps)
    return _mm("pd,vd->pv", x, embed, mode)


def logits_at(params, cfg: dict, tokens: np.ndarray, positions: np.ndarray,
              mode: str = "f32") -> np.ndarray:
    """Logits (len(positions), vocab) of one sequence at the given
    positions, each predicting the token after it."""
    m = dims(cfg)
    mk = tuple(sorted(m.items()))
    S = len(tokens)
    S_pad = -(-S // PAD_TO) * PAD_TO
    toks = np.zeros(S_pad, np.int32)
    toks[:S] = tokens
    P = len(positions)
    P_pad = -(-P // POS_PAD) * POS_PAD
    pos = np.zeros(P_pad, np.int32)
    pos[:P] = positions
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    h = _embed(params["embed"], jnp.asarray(toks), mode=mode)
    for l in range(m["L"]):
        h = _layer(params["layers"], l, h, m=_Frozen(mk), eps=eps,
                   theta=theta, mode=mode)
    out = _head(params["final_norm"], params["embed"], h, jnp.asarray(pos),
                eps=eps, mode=mode)
    return np.asarray(out)[:P]


class _Frozen(dict):
    """A hashable view of the dims dict, for a static jit argument."""

    def __init__(self, items):
        super().__init__(items)
        self._key = tuple(items)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key == other._key
