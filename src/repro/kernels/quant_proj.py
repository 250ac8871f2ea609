"""Pallas TPU kernel: group-wise asymmetric INT-b quantize-dequantize —
the Proj_{C_INTb} projection (AWQ/GPTQ convention, group_size=128).

One VMEM pass: per (row, group) min/max reduction → scale/zero → round/
clamp → dequant, all fused. Groups tile the lane dimension so the
reductions are segment-local; no scratch, no cross-block communication.

Grid: (rows/bm, d_in/bn) with bn a multiple of group_size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(z_ref, out_ref, *, bits: int, group: int):
    z = z_ref[...].astype(jnp.float32)
    bm, bn = z.shape
    g = z.reshape(bm, bn // group, group)
    qmax = float(2 ** bits - 1)
    gmax = g.max(axis=-1, keepdims=True)
    gmin = g.min(axis=-1, keepdims=True)
    scale = jnp.maximum((gmax - gmin) / qmax, 1e-8)
    zero = jnp.clip(jnp.round(-gmin / scale), 0.0, qmax)
    q = jnp.clip(jnp.round(g / scale) + zero, 0.0, qmax)
    deq = (q - zero) * scale
    out_ref[...] = deq.reshape(bm, bn).astype(out_ref.dtype)


def quant_project(z: jax.Array, bits: int, group_size: int = 128, *,
                  bm: int = 128, bn: int = 512,
                  interpret: bool = False) -> jax.Array:
    rows, d = z.shape
    assert d % group_size == 0, (d, group_size)
    bn = max(group_size, (min(bn, d) // group_size) * group_size)
    bm = min(bm, rows)
    pm = (-rows) % bm
    pn = (-d) % bn
    if pm or pn:
        # pad columns by replicating the row's first group so padded groups
        # quantize harmlessly; sliced off below either way
        z = jnp.pad(z, ((0, pm), (0, pn)))
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits, group=group_size),
        grid=((rows + pm) // bm, (d + pn) // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows + pm, d + pn), z.dtype),
        name="quant_proj",
        interpret=interpret,
    )(z)
    return out[:rows, :d]


__all__ = ["quant_project"]
