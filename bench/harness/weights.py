"""Seeded float weights for the program's parameter tree, made on the device.

The layout (which leaves exist, their shapes, dtypes and roles) is read
from the program with ``jax.eval_shape``; the values come from here, so
the reference can be handed the same weights without taking anything the
program made.  The scales are ``init_pipeline``'s:

* a matmul weight ``(..., N, K)`` is normal with std ``K ** -0.5``
  (``init_linear`` and ``init_conv``), a token embedding std 0.02;
* biases and norm offsets are 0, norm gains 1.

All weights come out of one jitted call: leaves of one shape and scale
share one draw, so the program holds a few dozen random draws, not one
per leaf.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import DictKey, FlattenedIndexKey

EMBED_STD = 0.02


def key_words(seed: int, stream: str) -> int:
    """A 32-bit word for one named stream of a run's randomness.  Any
    whole number is a valid seed, also beyond 64 bits."""
    tag = [ord(c) for c in stream]
    ss = np.random.SeedSequence([int(seed) & (2 ** 64 - 1),
                                 int(seed) >> 64, *tag])
    return int(ss.generate_state(1, np.uint32)[0])


def _rules(shapes, is_linear):
    """Per array leaf (in ``tree_leaves`` order): ('normal', std),
    ('zeros',) or ('ones',)."""
    rules = []
    for path, node in jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=is_linear)[0]:
        if is_linear(node):
            w_std = EMBED_STD if node.role == "embed" else \
                node.w.shape[-1] ** -0.5
            for cpath, _ in jax.tree_util.tree_flatten_with_path(node)[0]:
                idx = cpath[-1]
                assert isinstance(idx, FlattenedIndexKey), cpath
                rules.append(("normal", w_std) if idx.key == 0
                             else ("zeros",))
            continue
        last = path[-1]
        if not (isinstance(last, DictKey) and last.key in ("g", "b")):
            raise ValueError("no init rule for leaf "
                             + jax.tree_util.keystr(path))
        rules.append(("ones",) if last.key == "g" else ("zeros",))
    return rules


def maker(layout_fn, is_linear):
    """``make(key) -> tree``: one jitted program for ``layout_fn()``'s
    tree.  ``layout_fn`` returns the tree of ``ShapeDtypeStruct``s."""
    shapes = layout_fn()
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    rules = _rules(shapes, is_linear)
    assert len(rules) == len(leaves)
    groups: dict[tuple, list[int]] = {}
    for i, (leaf, rule) in enumerate(zip(leaves, rules)):
        if rule[0] == "normal":
            groups.setdefault((leaf.shape, str(leaf.dtype), rule[1]),
                              []).append(i)

    @jax.jit
    def make(key):
        out = [None] * len(leaves)
        for gi, ((shape, dtype, std), idxs) in enumerate(sorted(
                groups.items(), key=lambda kv: kv[1][0])):
            draw = jax.random.normal(jax.random.fold_in(key, gi),
                                     (len(idxs), *shape), jnp.float32)
            draw = (draw * std).astype(dtype)
            for j, i in enumerate(idxs):
                out[i] = draw[j]
        for i, (leaf, rule) in enumerate(zip(leaves, rules)):
            if rule[0] != "normal":
                fill = jnp.ones if rule[0] == "ones" else jnp.zeros
                out[i] = fill(leaf.shape, leaf.dtype)
        return jax.tree_util.tree_unflatten(treedef, out)
    return make


def seed_key(seed: int) -> jax.Array:
    return jax.random.key(key_words(seed, "weights"), impl="rbg")
