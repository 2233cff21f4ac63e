"""Weights from the seed, made by the benchmark.

Every weight is an integer in [-127, 127] times a power of two chosen per
tensor. Such numbers are exact in bfloat16 and in float32, so the program's
bf16 copy and the reference's float32 copy of a weight are the same number,
however either was computed: the integers come from JAX's counter-based
generator and nothing after it rounds.

A model's weights are a list of leaves (``Leaf``), each a path into the
program's parameter tree, a shape per layer and a scale. A stacked leaf
holds one slice per layer, drawn from ``fold_in(leaf key, layer)``, so the
reference can draw layer ``l`` alone and get the same numbers that
``make_params`` put in slice ``l``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Sequence, Tuple

LEVELS = 127
UNIFORM_STD = math.sqrt(LEVELS * (LEVELS + 1) / 3.0)   # std of U{-127..127}


@dataclasses.dataclass(frozen=True)
class Leaf:
    path: Tuple[str, ...]           # keys into the parameter tree
    shape: Tuple[int, ...]          # one layer's shape (the whole leaf if not stacked)
    std: float                      # the target spread of the values
    stacked: bool = True            # one slice per layer along a new axis 0
    offset: float = 0.0             # added before use: 1 + scale for norms

    @property
    def log2_step(self) -> int:
        return int(round(math.log2(self.std / UNIFORM_STD)))


def root_key(seed: int):
    """A raw threefry key from any whole number (64 bits are kept)."""
    import jax.numpy as jnp

    s = int(seed) & ((1 << 64) - 1)
    return jnp.asarray([s >> 32, s & 0xFFFFFFFF], dtype=jnp.uint32)


def draw(key, shape: Sequence[int], log2_step: int, dtype):
    import jax
    import jax.numpy as jnp

    ints = jax.random.randint(key, tuple(shape), -LEVELS, LEVELS + 1, dtype=jnp.int32)
    return (ints.astype(jnp.float32) * jnp.float32(2.0 ** log2_step)).astype(dtype)


def leaf_key(root, index: int):
    import jax

    return jax.random.fold_in(root, index)


def draw_layer(root, leaves: Sequence[Leaf], index: int, layer: int, dtype):
    import jax

    lf = leaves[index]
    k = leaf_key(root, index)
    if lf.stacked:
        k = jax.random.fold_in(k, layer)
    return draw(k, lf.shape, lf.log2_step, dtype)


def _set(tree: Dict, path: Tuple[str, ...], value: Any) -> None:
    node = tree
    for p in path[:-1]:
        node = node.setdefault(p, {})
    node[path[-1]] = value


def make_params(leaves: Sequence[Leaf], n_layers: int, seed: int, dtype: str,
                wrap_stages=None):
    """The whole parameter tree in one jitted call, on the default device,
    in ``dtype``. ``wrap_stages`` turns the nested dict into the program's
    tree (for example, stages held in a list)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    def build(root):
        tree: Dict = {}
        for i, lf in enumerate(leaves):
            k = leaf_key(root, i)
            if lf.stacked:
                v = jax.vmap(lambda l, k=k, lf=lf: draw(
                    jax.random.fold_in(k, l), lf.shape, lf.log2_step, dt))(
                        jnp.arange(n_layers))
            else:
                v = draw(k, lf.shape, lf.log2_step, dt)
            _set(tree, lf.path, v)
        return wrap_stages(tree) if wrap_stages else tree

    return jax.jit(build)(root_key(seed))


def layer_weights(leaves: Sequence[Leaf], root, layer: int, dtype) -> Dict[Tuple[str, ...], Any]:
    """Layer ``layer``'s slices of the stacked leaves, with offsets added."""
    return {lf.path: draw_layer(root, leaves, i, layer, dtype) + lf.offset
            for i, lf in enumerate(leaves) if lf.stacked}


def global_weights(leaves: Sequence[Leaf], root, dtype) -> Dict[Tuple[str, ...], Any]:
    return {lf.path: draw_layer(root, leaves, i, 0, dtype) + lf.offset
            for i, lf in enumerate(leaves) if not lf.stacked}


def std_for_fan_in(fan_in: int) -> float:
    return 1.0 / math.sqrt(fan_in)


def norm_std() -> float:
    """Norm scales spread about 0.06 around their offset of 1."""
    return 0.06

