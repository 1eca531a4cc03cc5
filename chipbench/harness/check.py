"""Whether what the timed path served is correct.

Once the window has closed and the program is shut down, a sample of the
requests it finished (drawn from the seed, the longest always in it, and
every request that was preempted mid-prompt and resumed) is run through the
family's float32 reference (`families/<family>.py` `logits_at`) over its
prompt and the tokens the program served. At each served position the gap
is the reference's best logit minus the logit of the token the program put
there; the number compared is the widest gap.
Greedy decoding puts the argmax first, so a correct bfloat16 path stays
within rounding of the reference's best, and a wrong mask, position, cache
row or handoff does not.

The control (`mode="fp8"`) reads the same prompts and served tokens through
the reference computed in fp8, and takes the gap of the token fp8 puts
first at each position.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np


@dataclass
class Served:
    rid: int
    prompt: np.ndarray          # int32 prompt tokens
    tokens: List[int]           # served tokens in order, the first token
                                # (prefill) first, as the decode steps fed them
    preempted: bool = False


def sample(served: Sequence[Served], seed: int, budget: int) -> List[Served]:
    """The longest finished request, every preempted one (longest first, at
    most half the budget), then random others from the seed until about
    `budget` served tokens are in."""
    done = [s for s in served if len(s.tokens) >= 1]
    if not done:
        return []
    done.sort(key=lambda s: (-len(s.prompt), s.rid))
    pick = [done[0]]
    n = len(done[0].tokens)
    for s in done[1:]:
        if s.preempted and n < budget // 2:
            pick.append(s)
            n += len(s.tokens)
    rng = np.random.default_rng([seed, 2])
    rest = [s for s in done if s not in pick]
    for i in rng.permutation(len(rest)):
        if n >= budget:
            break
        pick.append(rest[i])
        n += len(rest[i].tokens)
    return pick


def gaps(fam, params, cfg: dict, s: Served,
         mode: str = "f32") -> Dict[str, np.ndarray]:
    """Per served position: the f32 reference's best logit minus the logit
    of the served token (`served`), and with mode "fp8" also minus the logit
    of the token the control puts first (`control`)."""
    toks = np.concatenate([s.prompt, np.asarray(s.tokens[:-1], np.int32)])
    n = len(s.prompt)
    pos = np.arange(n - 1, n - 1 + len(s.tokens))
    ref = fam.logits_at(params, cfg, toks, pos, "f32")
    best = ref.max(axis=-1)
    rows = np.arange(len(pos))
    out = {"served": best - ref[rows, np.asarray(s.tokens)]}
    if mode == "fp8":
        low = fam.logits_at(params, cfg, toks, pos, "fp8")
        out["control"] = best - ref[rows, low.argmax(axis=-1)]
    return out
