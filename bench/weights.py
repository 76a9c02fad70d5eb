"""Seeded weights for a configuration, made by the benchmark itself.

The benchmark hands the program weights it drew, so the reference can use
the same weights without taking anything the program made.  The program
only fixes the layout: the tree of names, shapes and dtypes that its own
serving initialiser would return, read with ``jax.eval_shape`` (nothing is
computed).  Every leaf is then drawn here, on the device, in one jitted
call, in the dtype it is served in:

- ``table`` (embedding, tied LM head): N(0, 0.02²);
- ``w`` (a linear, ``(..., d_in, d_out)``): N(0, 1/d_in);
- ``b`` (a bias): N(0, 0.02²), so a dropped bias shows;
- ``scale`` (a norm gain): 1 + N(0, 0.05²), so a dropped gain shows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _draw(key, path: str, shape, dtype):
    leaf = path.rsplit("/", 1)[-1]
    x = jax.random.normal(key, shape, jnp.float32)
    if leaf == "table":
        x = x * 0.02
    elif leaf == "w":
        x = x * shape[-2] ** -0.5
    elif leaf == "b":
        x = x * 0.02
    elif leaf == "scale":
        x = 1.0 + 0.05 * x
    else:
        raise ValueError(f"no rule to draw weight {path!r}")
    return x.astype(dtype)


def _path(keys) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in keys)


def make(layout, seed: int):
    """Draw every leaf of ``layout`` (a tree of ``ShapeDtypeStruct``) from
    ``seed`` on the default device, in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(layout)

    def draw(key):
        leaves = [
            _draw(jax.random.fold_in(key, i), _path(p), s.shape, s.dtype)
            for i, (p, s) in enumerate(flat)
        ]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(draw)(prng_key(seed))


def prng_key(seed: int):
    """A PRNG key from any non-negative whole number, including seeds past
    32 bits: the low and high 32-bit words are folded in one after the
    other."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0: {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))
