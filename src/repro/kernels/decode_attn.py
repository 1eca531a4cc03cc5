"""Flash-decode GQA attention Pallas TPU kernel.

serve_step hot-spot: one new query token per request attending to a long KV
cache. Decode is bandwidth-bound (the cache is streamed once), so the kernel:
  * parallelizes over (batch, kv_head) and streams KV blocks sequentially with
    online-softmax state in VMEM scratch;
  * processes all Qg = H/K query heads of a kv head together as the rows of a
    (Qg_pad x hd) tile so each streamed KV block is used by every query head
    that needs it (maximizes arithmetic intensity at fixed bandwidth);
  * Qg is padded to the f32 sublane minimum (8) — garbage rows are sliced off
    by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30


def _decode_kernel(scalars_ref,           # SMEM: per-row [kv_len] * B
                   q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref,
                   *, block_k: int, scale: float):
    ib = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    kv_len = scalars_ref[ib]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_lo = ik * block_k

    @pl.when(k_lo < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (qg_pad, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        k_pos = k_lo + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < kv_len
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
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


def _verify_kernel(scalars_ref,           # SMEM: per-row [kv_len] * B
                   q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref,
                   *, block_k: int, scale: float, qg: int, s_seq: int):
    """Multi-query variant of `_decode_kernel` for speculative verify: the
    (rows_pad, hd) query tile holds S consecutive positions x Qg heads, row
    r = s * qg + g scoring draft position s. Per-row causal bound: query s
    sits at absolute position kv_len + s, so its keys are k_pos <= kv_len + s
    — which both admits the earlier draft keys (written into the gathered
    view by the verify step) and excludes the later ones plus the padding
    tail past kv_len + S."""
    ib = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    kv_len = scalars_ref[ib]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_lo = ik * block_k

    @pl.when(k_lo < kv_len + s_seq)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (rows_pad, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        k_pos = k_lo + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = lax.broadcasted_iota(jnp.int32, s.shape, 0)
        q_pos = kv_len + row // qg                        # absolute query pos
        mask = k_pos <= q_pos
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
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


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_verify_attention(
    q: jax.Array,            # (B, S, H, hd) — S = draft_k + 1 query positions
    k: jax.Array,            # (B, T, K, hd) with draft K/V already written
    v: jax.Array,            # (B, T, K, hd)
    kv_len: jax.Array,       # (B,) committed prefix length (query 0's pos)
    *,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Speculative-verify flash decode: row b scores S consecutive query
    positions kv_len[b] .. kv_len[b] + S - 1 against its own KV view in one
    launch. Same grid/streaming structure as `flash_decode_attention` — the
    query tile just grows from Qg to S * Qg rows, so the drafted block rides
    the same KV bandwidth the single query already paid for. Returns
    (B, S, H, hd)."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    assert H % K == 0
    qg = H // K
    rows = S * qg
    rows_pad = max(8, -(-rows // 8) * 8)                   # f32 sublane minimum
    scale = 1.0 / (hd ** 0.5)

    block_k = min(block_k, max(T, 128))
    t_pad = -T % block_k
    qt = jnp.moveaxis(q.reshape(B, S, K, qg, hd), 2, 1)    # (B, K, S, qg, hd)
    qt = qt.reshape(B, K, rows, hd)
    if rows_pad != rows:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, rows_pad - rows), (0, 0)))
    kt = jnp.moveaxis(k, 2, 1)                             # (B, K, T, hd)
    vt = jnp.moveaxis(v, 2, 1)
    if t_pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
    nk = (T + t_pad) // block_k

    scalars = jnp.broadcast_to(
        jnp.asarray(kv_len, dtype=jnp.int32).reshape(-1), (B,))
    kernel = functools.partial(_verify_kernel, block_k=block_k, scale=scale,
                               qg=qg, s_seq=S)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, nk),
        in_specs=[
            pl.BlockSpec((1, 1, rows_pad, hd), lambda b, kh, ik, *_: (b, kh, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, kh, ik, *_: (b, kh, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, kh, ik, *_: (b, kh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows_pad, hd),
                               lambda b, kh, ik, *_: (b, kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows_pad, 128), jnp.float32),
            pltpu.VMEM((rows_pad, 128), jnp.float32),
            pltpu.VMEM((rows_pad, hd), jnp.float32),
        ],
    )

    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, rows_pad, hd), q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(scalars, qt, kt, vt)

    out = out[:, :, :rows].reshape(B, K, S, qg, hd)
    return jnp.moveaxis(out, 1, 2).reshape(B, S, H, hd)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode_attention(
    q: jax.Array,            # (B, H, hd) — one new token per request
    k: jax.Array,            # (B, T, K, hd)
    v: jax.Array,            # (B, T, K, hd)
    kv_len: jax.Array | int,
    *,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Flash-decode. Returns (B, H, hd).

    ``kv_len`` is either a scalar (every row attends to the same prefix — the
    original contract) or a (B,)-shaped array of per-row valid lengths, the
    ragged continuous-batching case: each resident stream masks its own KV
    tail, so one kernel launch serves the whole batch."""
    B, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    assert H % K == 0
    qg = H // K
    qg_pad = max(8, qg)                                    # f32 sublane minimum
    scale = 1.0 / (hd ** 0.5)

    block_k = min(block_k, max(T, 128))
    t_pad = -T % block_k
    qt = q.reshape(B, K, qg, hd)
    if qg_pad != qg:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, qg_pad - qg), (0, 0)))
    kt = jnp.moveaxis(k, 2, 1)                            # (B, K, T, hd)
    vt = jnp.moveaxis(v, 2, 1)
    if t_pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
    nk = (T + t_pad) // block_k

    # one kv_len per batch row (a scalar broadcasts to every row)
    scalars = jnp.broadcast_to(
        jnp.asarray(kv_len, dtype=jnp.int32).reshape(-1), (B,))
    kernel = functools.partial(_decode_kernel, block_k=block_k, scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, nk),
        in_specs=[
            pl.BlockSpec((1, 1, qg_pad, hd), lambda b, kh, ik, *_: (b, kh, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, kh, ik, *_: (b, kh, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, kh, ik, *_: (b, kh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qg_pad, hd),
                               lambda b, kh, ik, *_: (b, kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((qg_pad, 128), jnp.float32),
            pltpu.VMEM((qg_pad, 128), jnp.float32),
            pltpu.VMEM((qg_pad, hd), jnp.float32),
        ],
    )

    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, qg_pad, hd), q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(scalars, qt, kt, vt)

    return out[:, :, :qg].reshape(B, H, hd)
