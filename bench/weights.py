"""Random weights made by the benchmark, from the run's seed.

The benchmark makes the weights itself, so that the plain reference
(``reference.py``) takes nothing the program made.  The program only
dictates the layout: ``make`` fills the tree of shapes the program's
model expects (``layout``), leaf by leaf inside one jitted call on the
device, in the dtype each leaf is served in.

Draws: norm scales 1, the token embedding N(0, 0.02), every other matrix
N(0, 1/fan_in) with fan_in its second-to-last axis.

``layer`` gives the reference one decoder layer's weights by plain
names, from the program's split into unrolled leading layers
(``prologue``) and a stacked, pattern-periodic ``body``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

ONES = {"ln1", "ln2", "ln_x", "final_norm", "kv_norm", "q_norm", "k_norm",
        "q_norm_a"}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (jax.random.key keeps 32 bits)."""
    s = int(seed)
    key = jax.random.key(s & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (s >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, int(s < 0))


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def make(layout: Any, seed: int) -> Any:
    """Weights for ``layout`` (a tree of ShapeDtypeStructs) from ``seed``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(layout)

    def init(key):
        out = []
        for i, (path, sd) in enumerate(flat):
            name = _leaf_name(path)
            k = jax.random.fold_in(key, i)
            if name in ONES:
                out.append(jnp.ones(sd.shape, sd.dtype))
                continue
            scale = 0.02 if name == "embed" else \
                1.0 / np.sqrt(max(1, sd.shape[-2] if len(sd.shape) >= 2
                                  else sd.shape[-1]))
            out.append((jax.random.normal(k, sd.shape, jnp.float32)
                        * scale).astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(init)(seed_key(seed)))


def n_layers(params: Any) -> int:
    body = params["body"]
    reps = jax.tree.leaves(body[0])[0].shape[0] if body else 0
    return len(params["prologue"]) + reps * len(body)


def layer(params: Any, i: int) -> Dict[str, Any]:
    """Decoder layer ``i``'s weights: {"ln1", "ln2", "core": {...},
    "mlp": {...}} as the program stores them (no copy for prologue
    layers; one slice per leaf for body layers)."""
    pro = params["prologue"]
    if i < len(pro):
        return pro[i]
    j = i - len(pro)
    period = len(params["body"])
    return jax.tree.map(lambda a: a[j // period], params["body"][j % period])
