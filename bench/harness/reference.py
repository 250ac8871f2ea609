"""Plain float32 reference of the served decoder.

Straightforward ``jax.numpy``, imported from nothing of the program: its own
round-to-nearest INT4 quantizer over the seeded floats of the block module
(``bench/harness/blocks/``), whose ``layer`` and ``final_norm`` are the
block's mathematics. It runs after the window, layer by layer (one block's
floats live at a time) over every sampled sequence, at the matmul precision
it is given: ``highest`` for the reference, ``high`` (three bf16 passes)
for the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import weights as W

PAD = 256          # rows per head call
SEQ_PAD = 1024     # sequences are right-padded to a multiple of this


def rtn_int4(w: jax.Array, group: int) -> jax.Array:
    """Quantize-dequantize a stored (d_in, d_out) weight onto the
    asymmetric min/max INT4 grid, per output row and group of ``group``
    input columns; a stack (n, d_in, d_out) slice by slice."""
    wt = jnp.swapaxes(w, -1, -2)
    *lead, d_out, d_in = wt.shape
    g = wt.reshape(*lead, d_out, d_in // group, group)
    lo = g.min(axis=-1, keepdims=True)
    hi = g.max(axis=-1, keepdims=True)
    scale = jnp.maximum((hi - lo) / 15.0, 1e-8)
    zero = jnp.clip(jnp.round(-lo / scale), 0.0, 15.0)
    q = jnp.clip(jnp.round(g / scale) + zero, 0.0, 15.0)
    return jnp.swapaxes(((q - zero) * scale).reshape(wt.shape), -1, -2)


@functools.partial(jax.jit, static_argnames=("block", "dims", "group"))
def dequant_block(key, block, dims, group: int):
    """Block ``key``'s floats with its linears on the INT4 grid."""
    linears = {entry[0] for entry in dims.linears()}
    return {n: (rtn_int4(v, group) if n in linears else v)
            for n, v in block.block_f32(key, dims).items()}


@jax.jit
def _embed(table, ids):
    return jnp.take(table, ids, axis=0)


@functools.partial(jax.jit, static_argnames=("block", "dims"))
def _head_stats(x, start, targets, outer, block, dims):
    """At the PAD rows of x from ``start``: the best logit, the logit of
    each row's target token, the largest |logit| and the argmax."""
    rows = jax.lax.dynamic_slice_in_dim(x, start, PAD, axis=0)
    lg = block.final_norm(rows, outer, dims) @ outer["lm_head"]
    got = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return (lg.max(axis=-1), got, jnp.abs(lg).max(axis=-1),
            jnp.argmax(lg, axis=-1))


def token_stats(seed: int, block, dims, recipe: W.Recipe, seqs, starts,
                targets, precision: str = "highest",
                head_precision: str = ""):
    """Reference logits of every sampled sequence at positions
    ``starts[i]`` and after, reduced on the device: ``seqs[i]`` is prompt +
    served tokens but the last (the logits at position j predict token
    j + 1) and ``targets[i]`` one token id per such position. The blocks
    run at ``precision``, the head at ``head_precision`` (by default the
    same). Returns per sequence a dict of host arrays: ``best``, ``got``
    (the target's logit), ``absmax`` and ``argmax``."""
    key = W.base_key(seed)
    outer = jax.jit(block.outer_f32, static_argnames=("dims", "pair_offset"))(
        key, dims, recipe.head_pair_offset)
    with jax.default_matmul_precision(precision):
        hs = []
        for s in seqs:
            ids = np.zeros(-(-len(s) // SEQ_PAD) * SEQ_PAD, np.int32)
            ids[:len(s)] = s
            hs.append(_embed(outer["embed"], jnp.asarray(ids)))
        for i in range(dims.num_hidden_layers):
            w = dequant_block(jax.random.fold_in(key, i), block, dims,
                              recipe.group_size)
            hs = [block.layer(w, x, dims, i) for x in hs]
            del w
    with jax.default_matmul_precision(head_precision or precision):
        out = []
        for x, s, st, tg in zip(hs, seqs, starts, targets):
            n = len(s) - st
            parts = {"best": [], "got": [], "absmax": [], "argmax": []}
            r = 0
            while r < n:
                lo = min(st + r, x.shape[0] - PAD)   # the slice stays inside
                skip = st + r - lo
                take = min(PAD - skip, n - r)
                tgt = np.zeros(PAD, np.int32)
                tgt[skip:skip + take] = tg[r:r + take]
                vals = _head_stats(x, lo, jnp.asarray(tgt), outer, block,
                                   dims)
                for k, v in zip(parts, vals):
                    parts[k].append(np.asarray(v)[skip:skip + take])
                r += take
            out.append({k: np.concatenate(v) for k, v in parts.items()})
        return out
