"""End-to-end metric arithmetic over the requests of one window.

Each record is one request as the client saw it: when it was due, when its
first token came, when it finished, and its limits. A request that never
produced its first token counts as +inf in the normalised TTFT tail and as a
miss in goodput (the convention of `slo_frac_percentile` in the program's
`core/metrics.py`, copied here so that no program change can move it).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np


@dataclass
class Record:
    due: float                      # host clock (s)
    prompt_len: int
    output_len: int
    ttft_slo: float
    tbt_slo: float
    first_token: Optional[float] = None   # host clock (s)
    finish: Optional[float] = None
    mean_tpot: Optional[float] = None     # (finish - decode enqueue) / tokens

    @property
    def ttft(self) -> float:
        return math.inf if self.first_token is None \
            else self.first_token - self.due

    @property
    def met(self) -> bool:
        """Both limits met: TTFT within its limit and the mean time per
        output token within the TBT limit. Unfinished is a miss."""
        if self.first_token is None or self.ttft > self.ttft_slo:
            return False
        if self.output_len <= 0:
            return True
        return self.mean_tpot is not None and self.mean_tpot <= self.tbt_slo


def percentile(values: Sequence[float], q: float) -> float:
    """q-th percentile; with +inf in the sample it takes the nearest lower
    order statistic, which is inf exactly when the position lands in the
    inf tail (linear interpolation between two infs is nan)."""
    a = np.asarray(values, np.float64)
    if a.size == 0:
        return math.nan
    if np.isinf(a).any():
        return float(np.percentile(a, q, method="lower"))
    return float(np.percentile(a, q))


def goodput_rps(recs: Sequence[Record], seconds: float) -> float:
    return sum(r.met for r in recs) / seconds


def ttft_slo_frac(recs: Sequence[Record], q: float) -> float:
    return percentile([r.ttft / r.ttft_slo for r in recs], q)


def tbt_ms(recs: Sequence[Record], q: float) -> float:
    vals = [r.mean_tpot for r in recs
            if r.output_len > 0 and r.mean_tpot is not None]
    return percentile(vals, q) * 1e3


def lateness(due: Sequence[float], sent: Sequence[float]) -> List[float]:
    """Seconds each request was sent after it was due."""
    return [s - d for d, s in zip(due, sent)]
