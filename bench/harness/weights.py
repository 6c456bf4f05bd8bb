"""Seeded weights, made on the device in one jitted call.

The layout (names and shapes of the leaves) is the program's parameter
tree, read from its shapes alone; the values are the benchmark's own:

* a leaf named ``g`` (a norm's gain) is ones;
* every other leaf (the embedding ``e``, and the matrices, possibly stacked
  over layers) is uniform on 128 levels, ``(j - 63.5) / 64`` for
  ``j = 0 .. 127``, times the power of two nearest to ``sqrt(3) * std``:
  std 0.02 for the embedding and ``1 / sqrt(fan_in)`` for a matrix,
  ``fan_in`` being its second-to-last dimension.

Every value has at most 8 significant bits, so it is exact in bfloat16 and
float32 alike, and it is computed from random integer bits by exact
operations only: the same seed gives the same values bit for bit in any
program, on any backend, however the compiler fuses it.  (A normal draw
goes through an approximated inverse error function, whose last bits, and
so the bfloat16 rounding of some values, depend on how it is compiled.)
Leaf ``i`` (in the tree's flattening order) draws from
``fold_in(key(seed), i)`` and is cast to the leaf's dtype, the dtype the
program serves its weights in; ``widen=True`` gives the same values in
float32 for the reference.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["seed_words", "init_leaf", "make_init", "make_change_norms",
           "leaf_names", "leaf_norms", "row_norms"]


def seed_words(seed: int) -> jnp.ndarray:
    """A seed of up to 64 bits as two uint32 words (low, high)."""
    s = int(seed) % (1 << 64)
    return jnp.asarray([s & 0xFFFFFFFF, s >> 32], dtype=jnp.uint32)


def _key(words):
    return jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])


def leaf_names(tree) -> list[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def init_leaf(key, i: int, name: str, shape, dtype):
    last = name.rsplit("/", 1)[-1]
    if last == "g":
        return jnp.ones(shape, dtype)
    std = 0.02 if last == "e" else float(shape[-2]) ** -0.5
    scale = 2.0 ** round(math.log2(math.sqrt(3.0) * std))
    bits = jax.random.bits(jax.random.fold_in(key, i), shape, jnp.uint32)
    level = (bits >> 25).astype(jnp.int32).astype(jnp.float32)
    return ((level - 63.5) * (scale / 64.0)).astype(dtype)


def make_init(abstract, out_shardings=None, *, widen: bool = False):
    """A jitted ``f(seed_words) -> tree`` of ``abstract``'s layout.

    ``widen=True`` returns float32 leaves holding the stored-dtype values
    (what the float32 reference starts from)."""
    names = leaf_names(abstract)
    leaves, treedef = jax.tree.flatten(abstract)

    def f(words):
        key = _key(words)
        out = []
        for i, (name, leaf) in enumerate(zip(names, leaves)):
            x = init_leaf(key, i, name, leaf.shape, leaf.dtype)
            out.append(x.astype(jnp.float32) if widen else x)
        return treedef.unflatten(out)

    return jax.jit(f, out_shardings=out_shardings)


def leaf_norms(tree, scale: float = 1.0):
    """Per-leaf float32 L2 norms (a list in flattening order)."""
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) * scale
            for x in jax.tree.leaves(tree)]


def row_norms(x, scale: float = 1.0):
    """Float32 L2 norms of the rows (the last axis) of ``x``."""
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), -1)) * scale


def make_change_norms(abstract):
    """A jitted ``f(tree, seed_words) -> [||leaf - init leaf||]``: how far
    each float32 leaf of ``tree`` has moved from its seeded start (the
    stored-dtype values, widened)."""
    names = leaf_names(abstract)
    leaves = jax.tree.leaves(abstract)

    def f(tree, words):
        key = _key(words)
        out = []
        for i, (name, leaf, x) in enumerate(zip(names, leaves,
                                                jax.tree.leaves(tree))):
            x0 = init_leaf(key, i, name, leaf.shape, leaf.dtype)
            d = x.astype(jnp.float32) - x0.astype(jnp.float32)
            out.append(jnp.sqrt(jnp.sum(d * d)))
        return jnp.stack(out)

    return jax.jit(f)
