"""Operator-segmented prefill execution — the TPU adaptation of FlowPrefill's
operator-level preemption (§5.1, Fig. 6).

On GPU the paper inserts cooperative preemption checks between CUDA kernel
launches. Under JAX/XLA the finest safe host-visible boundary is the dispatch
boundary of a compiled computation, so we compile the prefill as a sequence of
per-operator jitted segments over an explicit device-resident ExecState and let
the host check the preemption flag between dispatches. Suspension keeps the
state pytree alive on device (zero-copy); resume continues from the cursor.
Each segment consumes (donates) the K/V cache and `h_full` it is given and
returns them updated in place, so a caller holds only the newest state: a
reference to a cache leaf taken before a `step` is dead after it.

Operator sets (paper §5.1 / §6.5 exactly):
    dense:  qkv_proj | attn | o_proj | gate_up_proj | down_proj
    moe:    qkv_proj | attn | o_proj | gate | experts
Boundary granularity is configurable (op / layer / block-k / whole) to
reproduce the paper's Fig. 12 operator-vs-layer comparison.

Supports chunked prefill (Fig. 15 interplay): `chunk_tokens > 0` splits the
prompt; each chunk runs all layers with q_offset resumption via the flash
kernel's kv_len/q_offset scalars.

Prefix-cache resumption: ``start(..., prefix_len=P, prefix_k/v=...)`` seeds
the first P cache positions with KV gathered from a shared prefix cache and
starts the chunk loop at operator offset P — the same q_offset mechanism
chunking already uses, so a P-token prefix hit is pure skipped compute
(attention still reads the seeded prefix through kv_len). P is capped at
prompt_len - 1 by callers: the last position must be computed live for the
first-token logits.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.core.tracing import jit_named
from repro.kernels import ops as kops
from repro.models import layers as L
from repro.models.model import _project_qkv, embed_tokens, lm_head

State = Dict[str, Any]

DENSE_OPS = ("qkv_proj", "attn", "o_proj", "gate_up_proj", "down_proj")
MOE_OPS = ("qkv_proj", "attn", "o_proj", "gate", "experts")
# the state's large buffers: donated to every segment program, which returns
# them aliased (written in place or passed through, never copied)
CACHE = ("k_cache", "v_cache", "h_full")


def op_names(cfg: ModelConfig) -> Tuple[str, ...]:
    if cfg.num_experts:
        if cfg.moe_layer_freq != 1:
            raise NotImplementedError(
                "segmented executor supports uniform MoE stacks (freq=1)")
        return MOE_OPS
    if cfg.family in ("dense", "vlm"):
        return DENSE_OPS
    raise NotImplementedError(
        f"segmented executor: family {cfg.family!r} not wired "
        "(mechanism generalizes; see DESIGN.md §4)")


# ---------------------------------------------------------------------------
# Per-operator functions: fn(stacked_layer_params, state, layer_idx, q_offset)
# ---------------------------------------------------------------------------


def _layer(params: Dict, l: jax.Array) -> Dict:
    return jax.tree.map(lambda x: x[l], params)


def _make_op_fns(cfg: ModelConfig, attn_impl: str) -> Dict[str, Callable]:
    K, hd, H = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_heads

    def qkv_proj(pl_, st, l, off):
        p = _layer(pl_, l)
        x = L.rms_norm(st["h"], p["ln1"], cfg.norm_eps)
        return dict(st, tmp=_project_qkv(cfg, p, x))

    def attn(pl_, st, l, off):
        q, k, v = st["tmp"]
        B, Sc = q.shape[:2]
        positions = off + jnp.arange(Sc)[None, :]
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        kc = lax.dynamic_update_slice(
            st["k_cache"], k[None].astype(st["k_cache"].dtype),
            (l, 0, off, 0, 0))
        vc = lax.dynamic_update_slice(
            st["v_cache"], v[None].astype(st["v_cache"].dtype),
            (l, 0, off, 0, 0))
        out = kops.prefill_attention(
            q, kc[l], vc[l], q_offset=off, kv_len=off + Sc,
            causal=True, local_window=cfg.local_window, impl=attn_impl)
        return dict(st, tmp=out.reshape(B, Sc, H * hd), k_cache=kc, v_cache=vc)

    def o_proj(pl_, st, l, off):
        p = _layer(pl_, l)
        h = st["h"] + jnp.einsum("bsq,qd->bsd", st["tmp"], p["wo"])
        return dict(st, h=h, tmp=None)

    def gate_up_proj(pl_, st, l, off):
        p = _layer(pl_, l)
        x = L.rms_norm(st["h"], p["ln2"], cfg.norm_eps)
        gu = jnp.einsum("bsd,dzf->bszf", x, p["wi"])
        return dict(st, tmp=jax.nn.silu(gu[..., 0, :]) * gu[..., 1, :])

    def down_proj(pl_, st, l, off):
        p = _layer(pl_, l)
        h = st["h"] + jnp.einsum("bsf,fd->bsd", st["tmp"], p["wd"])
        return dict(st, h=h, tmp=None)

    def gate(pl_, st, l, off):
        p = _layer(pl_, l)
        x = L.rms_norm(st["h"], p["ln2"], cfg.norm_eps)
        w, idx, _ = L.moe_router(x, p["router"], cfg.experts_per_token)
        return dict(st, tmp=(x, w, idx))

    def experts(pl_, st, l, off):
        p = _layer(pl_, l)
        x, w, idx = st["tmp"]
        y = L.moe_apply(x, w, idx, p["wi"], p["wd"],
                        k=cfg.experts_per_token,
                        capacity_factor=cfg.moe_capacity_factor,
                        min_capacity=cfg.moe_min_capacity)
        return dict(st, h=st["h"] + y, tmp=None)

    return {"qkv_proj": qkv_proj, "attn": attn, "o_proj": o_proj,
            "gate_up_proj": gate_up_proj, "down_proj": down_proj,
            "gate": gate, "experts": experts}


# ---------------------------------------------------------------------------
# Execution plan + task
# ---------------------------------------------------------------------------


@dataclass
class PrefillTask:
    """A (possibly batched) prefill execution with device-resident state.
    The Execution Pool advances `cursor`; suspension is simply ceasing to
    dispatch — the state pytree stays alive on device."""
    state: State
    prompt_len: int
    n_chunks: int
    chunk: int
    total_segments: int
    start_offset: int = 0        # first token computed (prefix-cache hit:
                                 # positions < start_offset were seeded)
    cursor: int = 0
    logits: Optional[jax.Array] = None
    # representative output of the last dispatched segment — the Execution
    # Pool uses it to bound dispatch-ahead depth (bounded preemption latency
    # under async dispatch)
    sync_token: Optional[jax.Array] = None

    @property
    def done(self) -> bool:
        return self.cursor >= self.total_segments

    @property
    def progress(self) -> float:
        return self.cursor / max(self.total_segments, 1)


class SegmentedPrefill:
    """Preemptible prefill executor for one model instance.

    granularity: "op" (paper default) | "layer" | "block<k>" | "whole"
    chunk_tokens: 0 = no chunking (operator boundaries only), else chunked
                  prefill combined with operator preemption (paper Fig. 15).
    """

    def __init__(self, params, cfg: ModelConfig, *, max_seq: int,
                 granularity: str = "op", chunk_tokens: int = 0,
                 attn_impl: str = "xla", cache_dtype=jnp.float32):
        self.params = params
        self.cfg = cfg
        self.max_seq = max_seq
        self.granularity = granularity
        self.chunk_tokens = chunk_tokens
        self.cache_dtype = cache_dtype
        self.ops = op_names(cfg)
        op_fns = _make_op_fns(cfg, attn_impl)

        # group ops into jitted segments according to granularity; each
        # group's program is named after it (`prefill_attn`, `prefill_layer`)
        per_layer = [op_fns[name] for name in self.ops]
        if granularity == "op":
            groups: List[List[Callable]] = [[f] for f in per_layer]
            self._group_names = list(self.ops)
        elif granularity == "layer" or granularity.startswith("block") \
                or granularity == "whole":
            groups = [per_layer]           # block factor applied below
            self._group_names = [granularity]
        else:
            raise ValueError(granularity)

        self._block_layers = 1
        if granularity.startswith("block"):
            self._block_layers = int(granularity[len("block"):] or 2)
        elif granularity == "whole":
            self._block_layers = cfg.num_layers

        def make_segment(fns, n_layers, name):
            def seg(pl_, cache, act, l0, off, chunk_end=False):
                st = dict(act, **cache)
                for i in range(n_layers):
                    l = l0 + i
                    for f in fns:
                        st = f(pl_, st, l, off)
                if chunk_end:                   # the chunk's hidden rows
                    st["h_full"] = lax.dynamic_update_slice(
                        st["h_full"], st["h"].astype(st["h_full"].dtype),
                        (0, off, 0))
                return ({k: st[k] for k in CACHE},
                        {"h": st["h"], "tmp": st["tmp"]})
            return jit_named(f"prefill_{name}", seg, donate_argnums=1,
                             static_argnames="chunk_end")

        self._segments = [make_segment(g, self._block_layers, name)
                          for g, name in zip(groups, self._group_names)]
        self._segments_per_chunk = (
            (cfg.num_layers + self._block_layers - 1) // self._block_layers
            * len(self._segments))

        def start_fn(params_, tokens, vision_embeds=None):
            h = embed_tokens(cfg, params_, tokens)
            if cfg.family == "vlm" and vision_embeds is not None:
                P_ = vision_embeds.shape[1]
                h = h.at[:, :P_, :].set(vision_embeds.astype(h.dtype))
            return h

        def head_fn(params_, h_full, lens):
            # per-request last valid position (batched requests are padded)
            B = h_full.shape[0]
            h_last = h_full[jnp.arange(B), lens - 1][:, None, :]
            return lm_head(cfg, params_, h_last)[:, 0]

        self._start_fn = jit_named("prefill_embed", start_fn)
        self._head_fn = jit_named("prefill_head", head_fn)

    # --- plan geometry -------------------------------------------------------
    def n_chunks(self, prompt_len: int, prefix_len: int = 0) -> int:
        todo = prompt_len - prefix_len
        if not self.chunk_tokens:
            return 1
        return (todo + self.chunk_tokens - 1) // self.chunk_tokens

    def segments_for(self, prompt_len: int, prefix_len: int = 0) -> int:
        return self.n_chunks(prompt_len, prefix_len) \
            * self._segments_per_chunk + 1                          # +head

    # --- lifecycle -------------------------------------------------------------
    def start(self, tokens: jax.Array, vision_embeds=None, lens=None,
              prefix_len: int = 0, prefix_k=None,
              prefix_v=None) -> PrefillTask:
        """Begin a prefill. ``prefix_len > 0`` resumes over a cached prompt
        prefix: `prefix_k`/`prefix_v` (nL, B, prefix_len, K, hd) seed the
        first positions of the KV cache and the chunk loop starts at
        operator offset `prefix_len` — suffix-only compute. Requires
        prefix_len < min(lens) (the last token's logits need a live pass)
        and no vision embeds (a VLM's vision span must be recomputed)."""
        B, S = tokens.shape
        cfgc = self.cfg
        K, hd = cfgc.num_kv_heads, cfgc.resolved_head_dim
        nL = cfgc.num_layers
        kc = jnp.zeros((nL, B, self.max_seq, K, hd), self.cache_dtype)
        vc = jnp.zeros_like(kc)
        if prefix_len:
            if vision_embeds is not None:
                raise ValueError("prefix resumption over vision embeds is "
                                 "not supported (recompute the vision span)")
            if prefix_len >= S:
                raise ValueError(f"prefix_len={prefix_len} must leave at "
                                 f"least one live token (prompt {S})")
            kc = kc.at[:, :, :prefix_len].set(
                prefix_k.astype(self.cache_dtype))
            vc = vc.at[:, :, :prefix_len].set(
                prefix_v.astype(self.cache_dtype))
        state: State = {
            "tokens": tokens,
            "lens": (jnp.full((B,), S, jnp.int32) if lens is None
                     else jnp.asarray(lens, jnp.int32)),
            "h": None,                    # set per-chunk
            "tmp": None,
            "k_cache": kc,
            "v_cache": vc,
            "h_full": jnp.zeros((B, S, cfgc.d_model), jnp.float32),
        }
        if vision_embeds is not None:
            state["vision_embeds"] = vision_embeds
        chunk = self.chunk_tokens or (S - prefix_len)
        task = PrefillTask(
            state=state, prompt_len=S, start_offset=prefix_len,
            n_chunks=self.n_chunks(S, prefix_len), chunk=chunk,
            total_segments=self.segments_for(S, prefix_len))
        return task

    def _chunk_bounds(self, task: PrefillTask, chunk_idx: int) -> Tuple[int, int]:
        lo = task.start_offset + chunk_idx * task.chunk
        hi = min(lo + task.chunk, task.prompt_len)
        return lo, hi

    def describe(self, task: PrefillTask) -> Dict[str, Any]:
        """The segment `step` dispatches next: its op group (`head` for the
        LM head) and chunk index."""
        seg_idx = task.cursor
        if seg_idx >= task.total_segments - 1:
            return {"op": "head", "chunk": task.n_chunks - 1}
        chunk_idx, within = divmod(seg_idx, self._segments_per_chunk)
        return {"op": self._group_names[within % len(self._segments)],
                "chunk": chunk_idx}

    def step(self, task: PrefillTask) -> bool:
        """Dispatch the next segment. Returns True when the task completed.
        This is the paper's operator boundary: the caller checks the preemption
        signal between calls."""
        if task.done:
            return True
        seg_idx = task.cursor
        spc = self._segments_per_chunk
        if seg_idx == task.total_segments - 1:              # lm_head
            task.logits = self._head_fn(self.params, task.state["h_full"],
                                        task.state["lens"])
            task.sync_token = task.logits
            task.cursor += 1
            return True

        chunk_idx, within = divmod(seg_idx, spc)
        lo, hi = self._chunk_bounds(task, chunk_idx)
        n_groups = len(self._segments)
        layer_block, group_idx = divmod(within, n_groups)
        l0 = layer_block * self._block_layers

        st = task.state
        h = st["h"]
        if within == 0:                                     # chunk begins: embed slice
            tokens = st["tokens"][:, lo:hi]
            ve = st.get("vision_embeds") if chunk_idx == 0 else None
            h = self._start_fn(self.params, tokens, ve)
        cache, act = self._segments[group_idx](
            self.params["layers"], {k: st[k] for k in CACHE},
            {"h": h, "tmp": st["tmp"]}, l0, lo,
            chunk_end=within == spc - 1)
        task.state = dict(st, **cache, **act)
        task.sync_token = act["h"]
        task.cursor += 1
        return task.done

    def run_all(self, task: PrefillTask) -> jax.Array:
        """Uninterrupted execution (baseline / tests)."""
        while not task.done:
            self.step(task)
        return task.logits

    def profile(self, lengths: Sequence[int]) -> List[float]:
        """Offline TTFT profile (paper §6.4): wall seconds of one
        uninterrupted single-prompt prefill per length in `lengths`, each
        timed after a warm-up run that compiles its shapes. Both runs end
        in `block_until_ready`, so the time covers the device work and not
        only the dispatch."""
        seconds = []
        for n in lengths:
            toks = jnp.zeros((1, n), jnp.int32)
            jax.block_until_ready(self.run_all(self.start(toks)))
            t0 = time.monotonic()
            jax.block_until_ready(self.run_all(self.start(toks)))
            seconds.append(time.monotonic() - t0)
        return seconds
