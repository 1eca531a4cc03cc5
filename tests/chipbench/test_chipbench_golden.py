"""The Qwen2 family as the benchmark reads it, pinned to values recorded
before the architecture moved into `families/qwen2.py`
(`data/qwen2_*`): the weights of the tiny cell (`chipbench_tiny.tiny`)
from seed 7, bit for bit; the float32 reference's and the fp8 control's
logits at that size for one fixed sequence; and, for `qwen2-1.5b.json`, the
FLOP and byte counts and the two readers that use them, on the recorded
v5e trace with the work that trace holds."""
import hashlib
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import tiny
from conftest import BENCH
from harness import flops, spec, trace, weights

DATA = BENCH.parent / "tests" / "chipbench" / "data"
CFG = spec.config("qwen2-1.5b")
FAM = spec.family("qwen2")


@pytest.fixture(scope="module")
def tiny_params():
    cfg = tiny()[4]
    return cfg, weights.make(FAM, cfg, 7, jnp.bfloat16)


def test_weights_are_bit_identical(tiny_params):
    _, params = tiny_params
    want = json.loads((DATA / "qwen2_tiny_weights.json").read_text())
    got = {"/".join(k.key for k in path):
           hashlib.sha256(np.asarray(x).view(np.uint16).tobytes()).hexdigest()
           for path, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert want["seed"] == 7
    assert got == want["sha256"]


@pytest.mark.parametrize("mode", ["f32", "fp8"])
def test_reference_logits(tiny_params, mode):
    cfg, params = tiny_params
    want = np.load(DATA / "qwen2_tiny_logits.npz")
    got = FAM.logits_at(params, cfg, want["tokens"], want["positions"], mode)
    np.testing.assert_allclose(got, want[mode], rtol=0, atol=1e-6)


def test_counts():
    want = json.loads((DATA / "qwen2_counts.json").read_text())
    layer = FAM.attention(CFG)[0]
    assert {n: flops.prefill_flops(FAM, CFG, int(n))
            for n in want["prefill_flops"]} == want["prefill_flops"]
    assert {c: flops.decode_flops(FAM, CFG, int(c))
            for c in want["decode_flops"]} == want["decode_flops"]
    got = {}
    for k in want["flash_prefill_cost"]:
        off, n = map(int, k.split(","))
        got[k] = list(flops.flash_prefill_cost(layer, off, n))
    assert got == want["flash_prefill_cost"]


def test_readers():
    """The recorded trace: one 1024-token prefill, then four decode steps
    of 8 rows."""
    want = json.loads((DATA / "qwen2_counts.json").read_text())["readers"]
    dt = trace.load(str(DATA / "prefill_decode.xplane.pb.gz"))[
        "/device:TPU:0"]
    ns = types.SimpleNamespace
    run = ns(start=0.0, end=10.0,
             sent=[(ns(prompt_len=1024), ns(first_token_time=1.0), None)],
             steps=[(2.0 + 0.1 * i, [(r, 1024 + i) for r in range(8)])
                    for i in range(4)],
             cfg=CFG, family=FAM, cell={"chunk": 512}, device_trace=dt,
             busy_s=trace.busy_ns(dt) * 1e-9, peaks=spec.peaks("TPU v5 lite"))
    got = {k: spec.metric_reader(k)(run) for k in want}
    assert got == want
