"""Operator-segmented executor correctness: segmented == fused, chunked ==
unchunked, preempt/resume == uninterrupted (bit-exact), granularity variants
agree, and every segment program updates the donated K/V cache and `h_full`
in place. These validate the mechanism that makes operator-level preemption
safe.
"""
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_tiny_config
from repro.models import init_params, prefill
from repro.models.segments import CACHE, SegmentedPrefill
from repro.serving.pool import ExecTask, ExecutionPool

B, S = 2, 48


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_tiny_config("llama3_8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    return cfg, params, tokens


@pytest.fixture(scope="module")
def moe_setup():
    cfg = get_tiny_config("qwen3_30b_a3b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    return cfg, params, tokens


def fused_logits(params, cfg, tokens):
    logits, _ = prefill(params, cfg, {"tokens": tokens}, max_seq=S,
                        cache_dtype=jnp.float32)
    return logits


@pytest.mark.parametrize("setup_name", ["dense_setup", "moe_setup"])
def test_segmented_matches_fused(setup_name, request):
    cfg, params, tokens = request.getfixturevalue(setup_name)
    ex = SegmentedPrefill(params, cfg, max_seq=S, granularity="op")
    task = ex.start(tokens)
    got = ex.run_all(task)
    want = fused_logits(params, cfg, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_chunked_matches_unchunked(dense_setup):
    cfg, params, tokens = dense_setup
    ex1 = SegmentedPrefill(params, cfg, max_seq=S, granularity="op")
    ex2 = SegmentedPrefill(params, cfg, max_seq=S, granularity="op",
                           chunk_tokens=16)
    l1 = ex1.run_all(ex1.start(tokens))
    l2 = ex2.run_all(ex2.start(tokens))
    assert ex2.segments_for(S) > ex1.segments_for(S)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("gran", ["layer", "block2", "whole"])
def test_granularities_agree(dense_setup, gran):
    cfg, params, tokens = dense_setup
    ref = SegmentedPrefill(params, cfg, max_seq=S, granularity="op")
    alt = SegmentedPrefill(params, cfg, max_seq=S, granularity=gran)
    l_ref = ref.run_all(ref.start(tokens))
    l_alt = alt.run_all(alt.start(tokens))
    np.testing.assert_allclose(np.asarray(l_ref), np.asarray(l_alt),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("setup_name", ["dense_setup", "moe_setup"])
@pytest.mark.parametrize("stop_frac", [0.2, 0.5, 0.8])
def test_suspend_resume_bit_exact(setup_name, stop_frac, request):
    """The core safety property of operator-level preemption: suspending at ANY
    operator boundary and resuming later is bit-identical to uninterrupted
    execution (state is preserved exactly; nothing is recomputed)."""
    cfg, params, tokens = request.getfixturevalue(setup_name)
    ex = SegmentedPrefill(params, cfg, max_seq=S, granularity="op",
                          chunk_tokens=16)

    t_full = ex.start(tokens)
    want = ex.run_all(t_full)

    t = ex.start(tokens)
    stop_at = int(t.total_segments * stop_frac)
    while t.cursor < stop_at:
        ex.step(t)
    # --- suspension point: state simply stays alive; nothing else happens ---
    jax.block_until_ready(jax.tree.leaves(t.state))
    # --- resume ---
    got = ex.run_all(t)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_per_request_lens_head(dense_setup):
    """Padded batch: first-token logits must come from each request's own last
    position, not the pad tail."""
    cfg, params, _ = dense_setup
    t1 = jax.random.randint(jax.random.PRNGKey(5), (1, 20), 0, cfg.vocab_size)
    ex = SegmentedPrefill(params, cfg, max_seq=S, granularity="op")
    # solo run of the short request
    solo = ex.run_all(ex.start(t1))
    # padded batch: same request + a longer one
    t2 = jax.random.randint(jax.random.PRNGKey(6), (1, S), 0, cfg.vocab_size)
    toks = jnp.concatenate(
        [jnp.pad(t1, ((0, 0), (0, S - 20))), t2], axis=0)
    batched = ex.run_all(ex.start(toks, lens=jnp.asarray([20, S])))
    np.testing.assert_allclose(np.asarray(batched[0]), np.asarray(solo[0]),
                               rtol=2e-5, atol=2e-5)


def _aliased_outputs(compiled) -> set:
    """Output leaf indices the compiled program aliases to a parameter."""
    head = compiled.as_text().split("\n", 1)[0]
    m = re.search(r"input_output_alias=\{(.*?)\}, entry", head)
    return {int(i) for i in re.findall(r"\{(\d+)\}:", m.group(1))} if m \
        else set()


def copied_shapes(compiled) -> set:
    """Result shapes of the compiled program's copy instructions."""
    return {tuple(int(d) for d in dims.split(","))
            for dims in re.findall(r"= \w+\[([\d,]+)\]\S* copy(?:-start)?\(",
                                   compiled.as_text())}


def cache_aliased(compiled) -> bool:
    """True when every leaf of the segment's returned cache dict (its
    first output) is aliased to a donated parameter."""
    paths = [p for p, _ in
             jax.tree_util.tree_flatten_with_path(compiled.out_info)[0]]
    want = {i for i, p in enumerate(paths) if p[0].idx == 0}
    return len(want) == len(CACHE) and want <= _aliased_outputs(compiled)


@pytest.mark.parametrize("setup_name", ["dense_setup", "moe_setup"])
@pytest.mark.parametrize("gran", ["op", "layer"])
def test_segments_update_cache_in_place(setup_name, gran, request):
    """Every segment program takes the K/V cache and `h_full` donated and
    returns them aliased, with no copy of their shapes left in the program;
    the chunk's last segment writes `h_full` in place too. A cache leaf
    held from before a dispatch is consumed by it."""
    cfg, params, tokens = request.getfixturevalue(setup_name)
    ex = SegmentedPrefill(params, cfg, max_seq=S, granularity=gran,
                          chunk_tokens=16)
    task = ex.start(tokens)
    big = {tuple(task.state[k].shape) for k in CACHE}
    layers = params["layers"]
    cache = {k: task.state[k] for k in CACHE}
    act = {"h": ex._start_fn(params, tokens[:, :16]), "tmp": None}
    for i, seg in enumerate(ex._segments):
        ends = (False, True) if i == len(ex._segments) - 1 else (False,)
        for end in ends:
            compiled = seg.lower(layers, cache, act, 0, 0,
                                 chunk_end=end).compile()
            assert cache_aliased(compiled), (seg.__name__, end)
            assert not big & copied_shapes(compiled), (seg.__name__, end)
        held = cache["k_cache"]
        cache, act = seg(layers, cache, act, 0, 0)
        assert held.is_deleted()
        assert not any(x.is_deleted() for x in jax.tree.leaves((cache, act)))


@pytest.mark.parametrize("setup_name", ["dense_setup", "moe_setup"])
@pytest.mark.parametrize("prefix_len", [0, 20])
def test_pool_preempt_resume_bit_exact(setup_name, prefix_len, request):
    """A prefill driven through the Execution Pool (dispatch window 2),
    suspended mid-chunk and resumed, from a seeded prefix or none, ends
    bit-identical to `run_all` on a fresh task: donation leaves no stale
    state leaf behind in the pool's window or the suspended task."""
    cfg, params, tokens = request.getfixturevalue(setup_name)
    ex = SegmentedPrefill(params, cfg, max_seq=S, granularity="op",
                          chunk_tokens=16)
    seed = ex.start(tokens)
    ex.run_all(seed)
    kw = {}
    if prefix_len:
        kw = dict(prefix_len=prefix_len,
                  prefix_k=seed.state["k_cache"][:, :, :prefix_len],
                  prefix_v=seed.state["v_cache"][:, :, :prefix_len])
    ref = ex.start(tokens, **kw)
    want = ex.run_all(ref)

    task = ex.start(tokens, **kw)
    stop_at = ex._segments_per_chunk + 2            # inside the 2nd chunk
    done = threading.Event()

    def step(et):
        finished = ex.step(et.prefill_task)
        if et.prefill_task.cursor == stop_at:
            pool.signal.request_preemption()
        return finished

    pool = ExecutionPool(step, lambda et: done.set(), dispatch_depth=2)
    try:
        et = ExecTask(prefill_task=task, requests=[])
        pool.submit(et)
        assert pool.signal.wait_ack(60)
        deadline = time.monotonic() + 60
        while et not in pool.preempted_tasks():
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert task.cursor == stop_at and not task.done
        assert not any(x.is_deleted() for x in jax.tree.leaves(task.state))
        pool.resume(et.task_id)
        assert done.wait(60)
    finally:
        pool.shutdown()
    np.testing.assert_array_equal(np.asarray(task.logits), np.asarray(want))
    for k in CACHE:
        np.testing.assert_array_equal(np.asarray(task.state[k]),
                                      np.asarray(ref.state[k]))
