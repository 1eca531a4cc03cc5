"""Jit'd dispatch wrappers for the Pallas kernels.

`impl` selects the execution path:
  "pallas"            — the TPU kernel (real hardware)
  "pallas_interpret"  — same kernel body, interpreted on CPU (tests)
  "xla"               — blocked pure-JAX flash (dry-run lowering path)
  "ref"               — naive oracle (small shapes only)
`default_impl()` chooses by platform: "pallas" when JAX's default backend is
a TPU, "xla" on any other backend. Tests select "pallas_interpret" by name.
"""
from __future__ import annotations

import jax

from repro.kernels import ref as ref_mod
from repro.kernels.decode_attn import flash_decode_attention, flash_verify_attention
from repro.kernels.flash_prefill import flash_prefill_attention
from repro.models.layers import blocked_attention, naive_attention


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_impl() -> str:
    return "pallas" if _on_tpu() else "xla"


def prefill_attention(q, k, v, *, q_offset=0, kv_len=None, causal=True,
                      local_window=0, impl: str | None = None,
                      block_q=128, block_k=128):
    """q: (B,Sq,H,hd); k/v: (B,T,K,hd) -> (B,Sq,H,hd)."""
    impl = impl or default_impl()
    if impl == "pallas":
        return flash_prefill_attention(
            q, k, v, q_offset, kv_len, causal=causal, local_window=local_window,
            block_q=block_q, block_k=block_k)
    if impl == "pallas_interpret":
        return flash_prefill_attention(
            q, k, v, q_offset, kv_len, causal=causal, local_window=local_window,
            block_q=block_q, block_k=block_k, interpret=True)
    if impl == "xla":
        return blocked_attention(q, k, v, causal=causal, q_offset=q_offset,
                                 local_window=local_window, kv_len=kv_len,
                                 block=min(1024, max(k.shape[1], 1)))
    if impl == "ref":
        return ref_mod.chunked_prefill_attention_ref(
            q, k, v, q_offset=q_offset, kv_len=kv_len, causal=causal,
            local_window=local_window)
    raise ValueError(f"unknown impl {impl!r}")


def decode_attention(q, k, v, kv_len, *, impl: str | None = None, block_k=512):
    """q: (B,H,hd); k/v: (B,T,K,hd) -> (B,H,hd)."""
    impl = impl or default_impl()
    if impl == "pallas":
        return flash_decode_attention(q, k, v, kv_len, block_k=block_k)
    if impl == "pallas_interpret":
        return flash_decode_attention(q, k, v, kv_len, block_k=block_k,
                                      interpret=True)
    if impl == "xla":
        out = blocked_attention(q[:, None], k, v, causal=False, kv_len=kv_len,
                                block=min(1024, max(k.shape[1], 1)))
        return out[:, 0]
    if impl == "ref":
        return ref_mod.decode_attention_ref(q, k, v, kv_len=kv_len)
    raise ValueError(f"unknown impl {impl!r}")


def verify_attention(q, k, v, kv_len, *, impl: str | None = None, block_k=512):
    """Speculative-verify attention: q (B,S,H,hd) holds S consecutive query
    positions kv_len[b]..kv_len[b]+S-1 per row; k/v (B,T,K,hd) already carry
    the draft K/V at those positions. Per-row causal masking -> (B,S,H,hd).

    The "xla" path routes through the naive reference rather than
    `blocked_attention`: the blocked flash mask lacks the per-row
    q_offset/kv_len broadcast the verify step needs, while
    `naive_attention` supports (B,)-shaped offsets natively and S is tiny
    (draft_k + 1), so the quadratic cost is irrelevant.
    """
    impl = impl or default_impl()
    if impl == "pallas":
        return flash_verify_attention(q, k, v, kv_len, block_k=block_k)
    if impl == "pallas_interpret":
        return flash_verify_attention(q, k, v, kv_len, block_k=block_k,
                                      interpret=True)
    if impl in ("xla", "ref"):
        return naive_attention(q, k, v, causal=True, q_offset=kv_len)
    raise ValueError(f"unknown impl {impl!r}")
