"""Operations and bytes the served work needs, from shapes alone.

`cfg` is a benchmark configuration (`chipbench/configs/<name>.json`): the
published sizes under their published keys. `fam` is its family module
(`chipbench/families/<family>.py`), which gives the counts that depend on
the architecture: `token_flops(cfg)`, the projections and MLP or experts of
one token through all layers (only the experts a token is routed to);
`head_flops(cfg)`, the output projection at one position; and
`attention(cfg)`, one `Attn` per attention layer. Every count is the
algorithm's, not the program's: padding, recomputation and copies do not
count.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple


class Attn(NamedTuple):
    """One attention layer: query heads, key/value heads, head size, and
    the keys each query reads: all before it (window 0), or the `window`
    positions ending at its own."""
    heads: int
    kv_heads: int
    head_dim: int
    window: int = 0


def attn_pairs_prefill(offset: int, n: int, window: int = 0) -> int:
    """Query-key pairs of n causal queries at positions offset..offset+n-1,
    each over the keys at and before it (the last `window` of them, where
    window > 0)."""
    full = n * offset + n * (n + 1) // 2
    if window <= 0:
        return full
    # positions p >= window read `window` keys instead of p + 1
    over = max(offset + n - max(offset, window), 0)
    first = max(offset, window)
    return full - (over * (first + 1) + over * (over - 1) // 2) + over * window


def attn_keys(offset: int, n: int, window: int = 0) -> int:
    """Keys that n queries at offset..offset+n-1 read between them."""
    if window <= 0:
        return offset + n
    return offset + n - max(offset - window + 1, 0)


def attn_flops(fam, cfg: dict, offset: int, n: int) -> int:
    """QK^T and PV of n causal queries at offset, all attention layers and
    heads."""
    return sum(4 * a.heads * a.head_dim
               * attn_pairs_prefill(offset, n, a.window)
               for a in fam.attention(cfg))


def prefill_flops(fam, cfg: dict, prompt_len: int) -> int:
    """A whole prompt: every token through every layer, causal attention,
    and the output projection at the last position (the first token)."""
    return (prompt_len * fam.token_flops(cfg)
            + attn_flops(fam, cfg, 0, prompt_len) + fam.head_flops(cfg))


def decode_flops(fam, cfg: dict, ctx: int) -> int:
    """One decoded token at position ctx (it attends ctx + 1 keys, or its
    window)."""
    return (fam.token_flops(cfg) + attn_flops(fam, cfg, ctx, 1)
            + fam.head_flops(cfg))


def flash_prefill_cost(a: Attn, offset: int, n: int,
                       itemsize: int = 2) -> Tuple[int, int]:
    """(flops, bytes) of one prefill attention call for ONE layer `a`: n
    queries at offset over their causal context. Bytes: q read, output
    written, and the K and V of the real context (the keys the queries
    read) read once."""
    flops = 4 * a.heads * a.head_dim * attn_pairs_prefill(offset, n,
                                                          a.window)
    nbytes = itemsize * (2 * n * a.heads * a.head_dim
                         + 2 * attn_keys(offset, n, a.window)
                         * a.kv_heads * a.head_dim)
    return flops, nbytes


def least_time(flops: int, nbytes: int, peak_flops: float,
               peak_bw: float) -> float:
    """Roofline: the larger of compute time at peak and memory time at the
    peak bandwidth."""
    return max(flops / peak_flops, nbytes / peak_bw)
