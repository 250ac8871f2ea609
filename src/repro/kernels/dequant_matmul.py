"""Pallas TPU kernel: fused int4 dequant-matmul  y = x · Wqᵀ — the serving
payoff of AWP compression (weights stay packed in HBM; nibbles are unpacked
and dequantized in VMEM right before the MXU contraction, so HBM traffic is
~4 bits/weight instead of 16).

Layout: packed (N, K/2) uint8 (low nibble = even k), scale/zero (N, K/group).
Grid (M/bm, N/bn, K/bk), f32 accumulator scratch, K innermost.
bk must be a multiple of group_size so each K-block sees whole groups.

Mosaic-friendly formulation (no lane-splitting reshapes):

- x is split into its even and odd columns outside the kernel, so the low
  nibbles contract against x[:, 0::2] and the high nibbles against
  x[:, 1::2] — two MXU contractions instead of interleaving the nibbles
  back into k order;
- uint8 widens through int32 (Mosaic has no uint8 → f32 cast);
- scale/zero arrive as a full-width (bn, K/group) block per N-tile and are
  expanded to per-lane values with masked lane selects
  (:func:`expand_groups`), never with a (…, G, group) reshape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def expand_groups(planes, first, n_groups: int, width: int, lanes: int):
    """Per-group planes (…, G) → per-lane values (…, lanes) for the
    ``n_groups`` consecutive groups starting at group ``first`` (static or
    traced), each ``width`` lanes wide. Built from lane-index masks and a
    masked lane reduction per group — exact (one nonzero term per sum) and
    free of the sub-128-lane reshapes the TPU compiler refuses."""
    gid = jax.lax.broadcasted_iota(jnp.int32, planes.shape, planes.ndim - 1)
    shape = planes.shape[:-1] + (lanes,)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    out = jnp.zeros(shape, planes.dtype)
    for s in range(n_groups):
        col = jnp.sum(jnp.where(gid == first + s, planes, 0),
                      axis=-1, keepdims=True)
        out = jnp.where(lane >= s * width, col, out)
    return out


def _kernel(xe_ref, xo_ref, wp_ref, scale_ref, zero_ref, y_ref, acc_ref,
            *, n_k: int, group: int, sg: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    packed = wp_ref[...].astype(jnp.int32)             # (bn, bk//2)
    lo = (packed & 0xF).astype(jnp.float32)            # even k
    hi = (packed >> 4).astype(jnp.float32)             # odd k
    half = packed.shape[1]
    # a group of `group` k's spans group//2 packed lanes
    scale = expand_groups(scale_ref[...], kk * sg, sg, group // 2, half)
    zero = expand_groups(zero_ref[...], kk * sg, sg, group // 2, half)
    nt = (((1,), (1,)), ((), ()))
    acc_ref[...] += (
        jax.lax.dot_general(xe_ref[...].astype(jnp.float32),
                            (lo - zero) * scale, nt,
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(xo_ref[...].astype(jnp.float32),
                              (hi - zero) * scale, nt,
                              preferred_element_type=jnp.float32))

    @pl.when(kk == n_k - 1)
    def _emit():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def _auto_bm(m: int) -> int:
    """M tile for the serving shapes: full 128 for prefill-sized M, the
    smallest f32-sublane multiple (8) covering M for decode (M = batch·1 —
    a 128-row tile would be >90% padding compute)."""
    return 128 if m >= 128 else -(-m // 8) * 8


def dequant_matmul(x: jax.Array, packed: jax.Array, scale: jax.Array,
                   zero: jax.Array, *, group_size: int = 128,
                   bm: int = 0, bn: int = 256, bk: int = 512,
                   interpret: bool = False) -> jax.Array:
    """x: (M, K) f32/bf16; packed: (N, K//2) uint8; scale/zero: (N, K//group).
    Returns (M, N) = x @ dequant(W)ᵀ. ``bm=0`` (default) picks the M tile
    from the shape — decode-shaped calls get an 8-row tile, not 128."""
    m, k = x.shape
    n = packed.shape[0]
    assert packed.shape[1] * 2 == k
    assert scale.shape == (n, k // group_size) == zero.shape
    assert group_size % 2 == 0
    bk = max(group_size, (min(bk, k) // group_size) * group_size)
    bm = bm or _auto_bm(m)
    bm, bn = min(bm, -(-m // 8) * 8), min(bn, n)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pn or pk:
        packed = jnp.pad(packed, ((0, pn), (0, pk // 2)))
        scale = jnp.pad(scale, ((0, pn), (0, pk // group_size)),
                        constant_values=1.0)
        zero = jnp.pad(zero, ((0, pn), (0, pk // group_size)))
    mp, np_, kp = m + pm, n + pn, k + pk
    n_k = kp // bk
    n_groups = kp // group_size
    scale = scale.astype(jnp.float32)
    zero = zero.astype(jnp.float32)

    x_spec = pl.BlockSpec((bm, bk // 2), lambda i, j, kk: (i, kk))
    plane_spec = pl.BlockSpec((bn, n_groups), lambda i, j, kk: (j, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, group=group_size,
                          sg=bk // group_size),
        grid=(mp // bm, np_ // bn, n_k),
        in_specs=[
            x_spec, x_spec,
            pl.BlockSpec((bn, bk // 2), lambda i, j, kk: (j, kk)),
            plane_spec, plane_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="dequant_matmul",
        interpret=interpret,
    )(x[:, 0::2], x[:, 1::2], packed, scale, zero)
    return out[:m, :n]


__all__ = ["dequant_matmul", "expand_groups"]
