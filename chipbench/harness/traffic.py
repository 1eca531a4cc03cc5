"""The one traffic generator. A traffic mix is a JSON file of parameters
(`chipbench/traffic/<name>.json`); this module turns it and a cell's
parameters into requests.

Every seed replays the same schedule: request sizes and arrival gaps are
drawn once from the mix's `base_seed`, and `--seed` draws only the prompt
tokens (and the weights). Permuting the schedule per seed moved which
requests fell in the window: across six seeds tokens/s spread by 30-45%
and the TTFT tail by 20-36%, against about 1% between two runs of one
seed, so the seed and not the system set the spread.

The length model is the paper's QwenTrace (FlowPrefill, Table 1): four task
classes with lognormal prompt lengths fitted to their published mean and
standard deviation, in their published ratios, each with its Table 2 TTFT
limit. Prompt lengths are rounded to the nearest point of a geometric grid
(in log space), because the program compiles one prefill program per
distinct prompt length: the grid is the finite set of shapes set-up warms.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np


@dataclass(frozen=True)
class Item:
    """One request as the generator sends it."""
    index: int              # position in the seed's order
    due: float              # seconds after traffic start
    prompt_len: int
    output_len: int
    task: str
    ttft_slo: float         # seconds
    tbt_slo: float          # seconds per output token (mean)


def lognormal_params(mean: float, std: float):
    """(mu, sigma) of the lognormal with this mean and standard deviation."""
    sigma2 = math.log(1.0 + (std / mean) ** 2)
    return math.log(mean) - sigma2 / 2.0, math.sqrt(sigma2)


def length_grid(lo: int, hi: int, ratio: float, multiple: int) -> List[int]:
    """Geometric grid from lo to hi (both included), each point rounded to
    a multiple of `multiple`."""
    pts = []
    x = float(lo)
    while x < hi:
        pts.append(max(multiple, int(round(x / multiple)) * multiple))
        x *= ratio
    pts.append(hi)
    return sorted(set(p for p in pts if lo <= p <= hi))


def to_grid(n: int, grid: Sequence[int]) -> int:
    """Nearest grid point in log space."""
    g = np.asarray(grid, np.float64)
    return int(g[np.argmin(np.abs(np.log(g) - math.log(max(n, 1))))])


def max_prompt(mix: dict, cell: dict) -> int:
    """Longest prompt the cell sends (at most its prefill's `max_seq`)."""
    return min(int(cell["max_prompt"]), int(cell["max_seq"]))


def prompt_grid(mix: dict, cell: dict) -> List[int]:
    p = mix["prompt"]
    return length_grid(int(p["min"]), max_prompt(mix, cell),
                       float(p["grid_ratio"]), int(p["grid_multiple"]))


def _draw_sizes(mix: dict, cell: dict, n: int, rng: np.random.Generator):
    """n (task, prompt_len, output_len) triples from the mix's distributions."""
    grid = prompt_grid(mix, cell)
    lo, hi = int(mix["prompt"]["min"]), max_prompt(mix, cell)
    classes: Dict[str, dict] = mix["classes"]
    names = sorted(classes)
    probs = np.asarray([classes[c]["ratio"] for c in names], np.float64)
    probs /= probs.sum()
    out_spec = mix["output"]
    omu, osig = lognormal_params(out_spec["mean"], out_spec["std"])
    sizes = []
    for _ in range(n):
        task = names[int(rng.choice(len(names), p=probs))]
        c = classes[task]
        mu, sig = lognormal_params(c["mean"], c["std"])
        plen = int(np.clip(int(rng.lognormal(mu, sig)), lo, hi))
        olen = int(np.clip(int(rng.lognormal(omu, osig)),
                           out_spec["min"], out_spec["max"]))
        sizes.append((task, to_grid(plen, grid), olen))
    return sizes


def open_loop(mix: dict, cell: dict, horizon: float) -> List[Item]:
    """Requests due in [0, horizon), Poisson arrivals at the cell's rate."""
    rate = float(cell["rate"])
    n = int(math.ceil(rate * horizon * 1.25)) + 16
    base = np.random.default_rng(int(mix["base_seed"]))
    sizes = _draw_sizes(mix, cell, n, base)
    dues = np.cumsum(base.exponential(1.0 / rate, n))
    return [Item(index=i, due=float(d), prompt_len=plen, output_len=olen,
                 task=task, ttft_slo=float(mix["classes"][task]["ttft_slo"]),
                 tbt_slo=float(mix["tbt_slo"]))
            for i, (d, (task, plen, olen)) in enumerate(zip(dues, sizes))
            if d < horizon]


def prompt_tokens(items: Sequence[Item], vocab: int,
                  seed: int) -> Dict[int, np.ndarray]:
    """Random prompt token ids per item index, drawn from the seed."""
    rng = np.random.default_rng([seed, 1])
    return {it.index: rng.integers(0, vocab, it.prompt_len, dtype=np.int32)
            for it in sorted(items, key=lambda it: it.index)}
