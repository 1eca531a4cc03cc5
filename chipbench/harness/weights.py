"""Random weights for a configuration, made on the device from the seed in
one jitted call, in the type they are served in.

The layout and the distribution of each leaf are the family's
(`families/<family>.py`: `shapes`, `distribution`); `run.py` checks the
layout against the program's own shapes before serving. The values are the
benchmark's: the reference reads the same arrays, and nothing here comes
from the program.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def make(fam, cfg: dict, seed: int, dtype=jnp.bfloat16):
    """The params pytree on the default device, from `seed`. Leaves are
    drawn in sorted path order, one key each; a leaf inside a group (the
    stacked layers) is drawn one layer at a time along its first axis."""
    tree = fam.shapes(cfg)
    flat = []

    def walk(prefix, node):
        for k in sorted(node):
            if isinstance(node[k], dict):
                walk(prefix + (k,), node[k])
            else:
                flat.append((prefix + (k,), node[k]))
    walk((), tree)

    def init(key):
        keys = jax.random.split(key, len(flat))
        out: Dict = {}
        for (path, shape), k in zip(flat, keys):
            mean, std = fam.distribution(path, cfg)

            def draw(k, shape=shape, mean=mean, std=std):
                x = mean + std * jax.random.normal(k, shape, jnp.float32)
                return x.astype(dtype)
            if len(path) > 1:
                # one layer at a time: the float32 draw of a stacked leaf
                # would otherwise sit whole in device memory
                x = jax.lax.map(lambda kk: draw(kk, shape[1:]),
                                jax.random.split(k, shape[0]))
            else:
                x = draw(k)
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = x
        return out

    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(init)(key)
