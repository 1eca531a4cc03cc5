"""Operations and bytes the served work needs, from shapes alone.

`cfg` is a benchmark configuration (`chipbench/configs/<name>.json`): the
published sizes under their published keys. Every count is the algorithm's,
not the program's: padding, recomputation and copies do not count.
"""
from __future__ import annotations

from typing import Tuple


def dims(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    return {"d": d, "L": int(cfg["num_hidden_layers"]), "H": heads,
            "K": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // heads),
            "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"])}


def layer_matmul_flops(cfg: dict) -> int:
    """Multiply-adds x 2 of one layer's projections for one token: q, k, v,
    o and the gated MLP (gate, up, down)."""
    m = dims(cfg)
    d, H, K, hd, F = m["d"], m["H"], m["K"], m["hd"], m["F"]
    return 2 * (d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * F)


def head_flops(cfg: dict) -> int:
    """The output projection for one position."""
    m = dims(cfg)
    return 2 * m["d"] * m["V"]


def attn_pairs_prefill(offset: int, n: int) -> int:
    """Query-key pairs of n causal queries at positions offset..offset+n-1,
    each over the keys at and before it."""
    return n * offset + n * (n + 1) // 2


def attn_flops(cfg: dict, pairs: int) -> int:
    """QK^T and PV over `pairs` query-key pairs, all layers and heads."""
    m = dims(cfg)
    return 4 * m["L"] * m["H"] * m["hd"] * pairs


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """A whole prompt: every token through every layer, causal attention,
    and the output projection at the last position (the first token)."""
    m = dims(cfg)
    return (prompt_len * m["L"] * layer_matmul_flops(cfg)
            + attn_flops(cfg, attn_pairs_prefill(0, prompt_len))
            + head_flops(cfg))


def decode_flops(cfg: dict, ctx: int) -> int:
    """One decoded token at position ctx (it attends ctx + 1 keys)."""
    m = dims(cfg)
    return (m["L"] * layer_matmul_flops(cfg) + attn_flops(cfg, ctx + 1)
            + head_flops(cfg))


def flash_prefill_cost(cfg: dict, offset: int, n: int,
                       itemsize: int = 2) -> Tuple[int, int]:
    """(flops, bytes) of one prefill attention call for ONE layer: n queries
    at offset over their causal context. Bytes: q read, output written, and
    the K and V of the real context (offset + n positions) read once."""
    m = dims(cfg)
    H, K, hd = m["H"], m["K"], m["hd"]
    flops = 4 * H * hd * attn_pairs_prefill(offset, n)
    nbytes = itemsize * (2 * n * H * hd + 2 * (offset + n) * K * hd)
    return flops, nbytes


def least_time(flops: int, nbytes: int, peak_flops: float,
               peak_bw: float) -> float:
    """Roofline: the larger of compute time at peak and memory time at the
    peak bandwidth."""
    return max(flops / peak_flops, nbytes / peak_bw)
