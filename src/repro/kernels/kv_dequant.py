"""Pallas TPU kernel: INT8 KV-cache dequant — the attention-read side of the
quantized page pools (``repro.serving.kv_cache``).

Layout mirrors ``dequant_matmul``'s weight side: codes (R, K) uint8 with
scale/zero (R, K/group) — the caller flattens the (L·)P·page leading dims
into rows R and folds heads into K, so K = Hk·D is lane-aligned for real
head dims. Grid (R/bm, K/bk), bk a multiple of group_size so each block sees
whole groups; pure VPU elementwise expansion, emitted as the requested
float dtype (HBM keeps the 1-byte codes, VMEM gets the floats). Codes widen
through int32 and scales expand with lane masks
(:func:`~repro.kernels.dequant_matmul.expand_groups`), the forms the TPU
compiler accepts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dequant_matmul import expand_groups


def _kernel(codes_ref, scale_ref, zero_ref, out_ref, *, group: int, sg: int):
    codes = codes_ref[...].astype(jnp.int32).astype(jnp.float32)  # (bm, bk)
    first = pl.program_id(1) * sg
    lanes = codes.shape[1]
    scale = expand_groups(scale_ref[...], first, sg, group, lanes)
    zero = expand_groups(zero_ref[...], first, sg, group, lanes)
    out_ref[...] = ((codes - zero) * scale).astype(out_ref.dtype)


def kv_dequant(codes: jax.Array, scale: jax.Array, zero: jax.Array, *,
               group_size: int, out_dtype=jnp.float32,
               bm: int = 256, bk: int = 512,
               interpret: bool = False) -> jax.Array:
    """codes: (R, K) uint8; scale/zero: (R, K//group) f16/f32 → (R, K) float."""
    r, k = codes.shape
    assert k % group_size == 0
    assert scale.shape == (r, k // group_size) == zero.shape
    bk = max(group_size, (min(bk, k) // group_size) * group_size)
    bm = min(bm, -(-r // 8) * 8)
    pr, pk = (-r) % bm, (-k) % bk
    if pr or pk:
        codes = jnp.pad(codes, ((0, pr), (0, pk)))
        scale = jnp.pad(scale, ((0, pr), (0, pk // group_size)),
                        constant_values=1.0)
        zero = jnp.pad(zero, ((0, pr), (0, pk // group_size)))
    rp, kp = r + pr, k + pk
    plane_spec = pl.BlockSpec((bm, kp // group_size), lambda i, j: (i, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, group=group_size, sg=bk // group_size),
        grid=(rp // bm, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            plane_spec, plane_spec,
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, kp), out_dtype),
        name="kv_dequant",
        interpret=interpret,
    )(codes, scale.astype(jnp.float32), zero.astype(jnp.float32))
    return out[:r, :k]


__all__ = ["kv_dequant"]
