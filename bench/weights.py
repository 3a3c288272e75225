"""Random weights from the seed, made by the benchmark in one jitted call
on the device, in the types they are served in, and handed to both the
program and the reference.

The tree has the program's layout (its parameter shapes, read with
``jax.eval_shape``); the values are the benchmark's own, so the reference
never depends on how the program initialises. Matrices are normal with
variance 1/fan-in, RMSNorm scales 1 + N(0, 0.1^2), and the QKV biases
and the embedding and output tables N(0, 0.02^2). Larger biases or tables
make every position's output collapse onto one repeated token (a shared
direction dominates the residual), which an output check can hardly tell
from a broken program; at these scales greedy outputs stay diverse.

A model family (``bench/families/<family>.py``) may bring rules of its own
for the leaves these do not know (latent projections, routers, experts):
a function ``weight_rule(path, shape, key)`` that returns the leaf's value
in its served type, or ``None`` to leave the leaf to the rules here. The
family is asked first; a leaf that no rule covers is refused with the
family's name and the leaf's whole path.
"""

from __future__ import annotations

import math
from pathlib import Path
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

#: leaves served in bfloat16 (matrices and tables); the rest stay float32
BF16_LEAVES = frozenset({"embed", "unembed", "wq", "wk", "wv", "wo",
                         "w_gate", "w_up", "w_down"})


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one wider than 32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _leaf(key, name: str, shape) -> Optional[jax.Array]:
    if name in ("embed", "unembed"):
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif name in BF16_LEAVES:
        x = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])
    elif name == "scale":
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name in ("bq", "bk", "bv"):
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:
        return None
    return x.astype(jnp.bfloat16 if name in BF16_LEAVES else jnp.float32)


def _value(family, key, path: Tuple[str, ...], shape) -> jax.Array:
    """The leaf at ``path``: the family's rule where it gives one, else the
    dense rules; refused where neither does."""
    rule = getattr(family, "weight_rule", None)
    x = rule(path, shape, key) if rule is not None else None
    if x is None:
        x = _leaf(key, path[-1], shape)
    if x is None:
        name = Path(family.__file__).stem if family is not None else "dense"
        raise ValueError(f"no rule for parameter leaf {'/'.join(path)!r} "
                         f"in model family {name!r}")
    return x


def _maker(init_fn, seed: int, family=None):
    key = seed_key(seed)
    shapes = jax.eval_shape(init_fn, key)
    paths = jax.tree_util.tree_flatten_with_path(shapes)[0]
    treedef = jax.tree_util.tree_structure(shapes)

    def make(key):
        leaves = [_value(family, jax.random.fold_in(key, i),
                         tuple(k.key for k in path), s.shape)
                  for i, (path, s) in enumerate(paths)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make, key


def make_params(init_fn, seed: int, out_shardings: Any = None,
                family=None) -> Any:
    """The weights for ``seed`` in the tree ``init_fn(key)`` would build;
    with ``out_shardings`` each leaf is made in its shards, so a model
    larger than one device never passes through one. ``family`` is the
    configuration's family module, asked first for each leaf's rule."""
    make, key = _maker(init_fn, seed, family)
    return jax.jit(make, out_shardings=out_shardings)(key)
