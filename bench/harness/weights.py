"""Seeded weights and token ids, made by the benchmark and never by the
program under test.

Every float weight of a block comes from the block module's ``block_f32``
(``bench/harness/blocks/``), keyed by the run seed and the block index, so
the plain reference can make the same floats again, block by block, after
the program's state is freed. Served weights are those floats quantized by
the program's own packer (round to nearest, INT4, groups of 128 along the
input dimension) inside one jitted call.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Recipe:
    """How the seeded weights are made: the INT4 group size, and the
    offset between the head's paired columns (the block's ``outer_f32``)."""
    group_size: int = 128
    head_pair_offset: float = 0.0

    @classmethod
    def from_config(cls, weights: dict) -> "Recipe":
        return cls(int(weights["group_size"]),
                   float(weights.get("head_pair_offset", 0.0)))


def base_key(seed: int) -> jax.Array:
    """A key from a seed of any size (seeds may exceed 32 bits):
    its low and high 32-bit words folded into a fixed key."""
    key = jax.random.key(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def quantize(w: jax.Array, group_size: int):
    """A stored linear packed INT4 by the program's own packer
    (``QTensor.from_dense``): (d_in, d_out) into one ``QTensor`` of shape
    (d_out, d_in); a stack (n, d_in, d_out) slice by slice into one whose
    children lead with n and whose aux shape is one slice's, the layout
    ``repro.models.layers.expert_apply`` reads."""
    from repro.quant import QTensor
    if w.ndim == 3:
        return jax.vmap(lambda s: quantize(s, group_size))(w)
    return QTensor.from_dense(w.T, bits=4, group_size=group_size)


def served_params(seed: int, block, dims, recipe: Recipe):
    """The program's parameter tree for serving: the ``block`` module's
    ``served_block`` of every block, its linears INT4 ``QTensor``s packed by
    :func:`quantize` from ``block_f32`` and stacked over the blocks, and its
    ``outer_f32`` in float32. One jitted call; a loop over blocks keeps one
    block's floats live at a time."""
    nl = dims.num_hidden_layers

    def one_block(key0, i):
        return block.served_block(
            block.block_f32(jax.random.fold_in(key0, i), dims),
            lambda w: quantize(w, recipe.group_size))

    @jax.jit
    def build(key0):
        shapes = jax.eval_shape(one_block, key0, 0)
        blocks = jax.tree.map(
            lambda s: jnp.zeros((nl,) + s.shape, s.dtype), shapes)

        def body(i, acc):
            new = one_block(key0, i)
            return jax.tree.map(
                lambda a, x: jax.lax.dynamic_update_index_in_dim(a, x, i, 0),
                acc, new)
        blocks = jax.lax.fori_loop(0, nl, body, blocks)
        return dict(block.outer_f32(key0, dims, recipe.head_pair_offset),
                    blocks=blocks)

    return build(base_key(seed))
