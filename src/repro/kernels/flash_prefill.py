"""Chunked-causal flash attention Pallas TPU kernel.

This is the `attn` operator of FlowPrefill's operator-level preemption set —
the dominant compute in prefill. One call processes a *query chunk* (the unit
chunked prefill executes between preemption checks) against the full prior
KV prefix, so the kernel natively supports q_offset > 0 resumption.

TPU mapping:
  grid = (B, H, n_q_blocks, n_kv_blocks), kv innermost ("arbitrary" semantics,
  sequential accumulation); q/k/v tiles live in VMEM via BlockSpec; the online
  softmax state (m, l, acc) lives in VMEM scratch that persists across the kv
  grid dimension. GQA is handled by the k/v index_map (kv head = q head // Qg)
  — no KV repetition in HBM. block_q x block_k default 128x128 to align the
  MXU (128x128 systolic array) and keep the working set
  (3 * 128 * head_dim * 4B + scores) well under VMEM (~16 MB).

Scalar prefetch carries (q_offset, kv_len) so one compiled kernel serves every
chunk position — preemption/resume never recompiles.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30


def _flash_kernel(scalars_ref,            # SMEM: [q_offset, kv_len]
                  q_ref, k_ref, v_ref,    # VMEM tiles
                  o_ref,                  # VMEM out tile
                  m_ref, l_ref, acc_ref,  # VMEM scratch
                  *, causal: bool, local_window: int,
                  block_q: int, block_k: int, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    q_offset = scalars_ref[0]
    kv_len = scalars_ref[1]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level skip tests (work avoidance: causal upper triangle, beyond
    # kv_len, or entirely below the local window)
    q_lo = q_offset + iq * block_q
    q_hi = q_lo + block_q - 1
    k_lo = ik * block_k
    k_hi = k_lo + block_k - 1
    skip = k_lo >= kv_len
    if causal:
        skip |= k_lo > q_hi
    if local_window:
        skip |= k_hi <= q_lo - local_window

    @pl.when(jnp.logical_not(skip))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        q_pos = q_lo + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_lo + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos
        if local_window:
            mask &= k_pos > q_pos - local_window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]                             # (bq, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)                       # kill -1e30 rows exactly
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "local_window", "block_q", "block_k", "interpret"))
def flash_prefill_attention(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, T, K, hd)
    v: jax.Array,            # (B, T, K, hd)
    q_offset: jax.Array | int = 0,
    kv_len: jax.Array | int | None = None,
    *,
    causal: bool = True,
    local_window: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Chunked-causal flash attention. Returns (B, Sq, H, hd)."""
    B, Sq, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    assert H % K == 0, (H, K)
    qg = H // K
    scale = 1.0 / math.sqrt(hd)
    kv_len = T if kv_len is None else kv_len

    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(T, 128))

    # pad to block multiples
    sq_pad = -Sq % block_q
    t_pad = -T % block_k
    qt = jnp.moveaxis(q, 2, 1)                            # (B, H, Sq, hd)
    kt = jnp.moveaxis(k, 2, 1)                            # (B, K, T, hd)
    vt = jnp.moveaxis(v, 2, 1)
    if sq_pad:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sq_pad), (0, 0)))
    if t_pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
    Sq_p, T_p = Sq + sq_pad, T + t_pad
    nq, nk = Sq_p // block_q, T_p // block_k

    scalars = jnp.array([q_offset, kv_len], dtype=jnp.int32)

    kernel = functools.partial(
        _flash_kernel, causal=causal, local_window=local_window,
        block_q=block_q, block_k=block_k, scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, iq, ik, *_: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik, *_: (b, h // qg, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik, *_: (b, h // qg, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, iq, ik, *_: (b, h, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),      # m
            pltpu.VMEM((block_q, 128), jnp.float32),      # l
            pltpu.VMEM((block_q, hd), jnp.float32),       # acc
        ],
    )

    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq_p, hd), q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(scalars, qt, kt, vt)

    out = jnp.moveaxis(out, 1, 2)                         # (B, Sq_p, H, hd)
    return out[:, :Sq]
