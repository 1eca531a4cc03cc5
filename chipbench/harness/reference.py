"""Parts of a plain float32 reference forward pass, shared by the family
modules (`chipbench/families/<family>.py`), each of which builds its
architecture's `logits_at` from them.

Straight `jax.numpy`: no kernel, cache or batching; one sequence, all its
positions, every matmul at HIGHEST precision so a TPU does not round
float32 operands to bfloat16. It imports nothing of the program.

A family runs its reference layer by layer, one jitted layer program per
padded length (`pad`), and attention in blocks of queries
(`causal_attention`), so that a long sequence fits beside the weights.
Right padding does not change earlier positions under the causal mask.

`mode="fp8"` is the control: every matmul operand (weights, activations,
queries, keys, probabilities, values) rounded to float8_e4m3fn under a
per-tensor scale, then multiplied in float32 — the precision step below the
bfloat16 the configurations serve in.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD_TO = 2048          # sequence length bucket (compiles one program each)
POS_PAD = 128          # position-count bucket of the output head
Q_BLOCK = 512          # queries per attention block
FP8_MAX = 448.0        # largest finite float8_e4m3fn


def operand(x, mode):
    """An operand as the mode computes it: float32, or rounded to fp8 under
    a per-tensor absmax scale."""
    x = x.astype(jnp.float32)
    if mode == "f32":
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(eq, a, b, mode):
    return jnp.einsum(eq, operand(a, mode), operand(b, mode),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


def rms(x, w, eps):
    """RMSNorm with the scale stored as an offset from 1."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def rope(x, pos, theta):
    """x (S, n, hd): rotate the two halves of each head by pos * freq."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_attention(q, k, v, mode):
    """Grouped-query causal softmax attention scaled by 1/sqrt(head_dim),
    in blocks of Q_BLOCK queries. q (S, H, hd), k and v (S, K, hd); query
    head j reads kv head j // (H // K). Returns (S, H * hd)."""
    S, H, hd = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    pos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = mm("qhd,khd->hqk", qb, k, mode) / jnp.sqrt(jnp.float32(hd))
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        return mm("hqk,khd->qhd", pr, v, mode)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK))
    return o.reshape(S, H * hd)


def pad(tokens: np.ndarray,
        positions: np.ndarray) -> Tuple[jax.Array, jax.Array]:
    """The tokens right-padded to a multiple of PAD_TO and the positions to
    a multiple of POS_PAD, so that a few programs serve every length; the
    caller keeps the first len(positions) rows of what it computes."""
    S_pad = -(-len(tokens) // PAD_TO) * PAD_TO
    toks = np.zeros(S_pad, np.int32)
    toks[:len(tokens)] = tokens
    P_pad = -(-len(positions) // POS_PAD) * POS_PAD
    pos = np.zeros(P_pad, np.int32)
    pos[:len(positions)] = positions
    return jnp.asarray(toks), jnp.asarray(pos)
