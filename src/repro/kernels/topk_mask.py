"""Pallas TPU kernel: row-wise hard thresholding H_k (exact top-k) via
vectorized threshold bisection — the projection of Eq. (5).

GPU implementations use radix-select in shared memory; the TPU-native
replacement is a fixed number of lane-parallel "count |z| ≥ τ" sweeps
(DESIGN.md §2): 40 bisection steps shrink [lo, hi) to ~1 ulp, then exact-k
is restored by keeping all entries > boundary plus the first (k − count)
boundary ties in index order (matching jax.lax.top_k's tie-breaking),
found by a second bisection over the index position.

Grid: one program per row block; the whole row strip lives in VMEM
(bm × d_in — ≤ 8×73728 f32 ≈ 2.3 MB for the largest assigned arch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BISECT_ITERS = 40


def _kernel(z_ref, out_ref, *, k: int):
    z = z_ref[...]
    mag = jnp.abs(z.astype(jnp.float32))
    d = mag.shape[-1]
    if k >= d:
        out_ref[...] = z
        return
    hi0 = mag.max(axis=-1, keepdims=True) + 1.0        # count(≥hi)=0 < k
    lo0 = jnp.zeros_like(hi0)                          # count(≥0)=d ≥ k

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((mag >= mid).astype(jnp.int32), axis=-1, keepdims=True)
        take_lo = cnt >= k                             # keep invariant
        lo2 = jnp.where(take_lo, mid, lo)
        hi2 = jnp.where(take_lo, hi, mid)
        return lo2, hi2

    lo, hi = jax.lax.fori_loop(0, _BISECT_ITERS, body, (lo0, hi0))
    definite = mag >= hi                               # strictly above ties
    n_def = jnp.sum(definite.astype(jnp.int32), axis=-1, keepdims=True)
    boundary = jnp.logical_and(mag >= lo, jnp.logical_not(definite))
    need = k - n_def                                   # ties to keep, ≥ 0
    # the first `need` ties in index order: bisect the smallest position p
    # with count(boundary ∧ idx ≤ p) ≥ need — lane-parallel counts, the same
    # sweep as above (Mosaic has no cumsum)
    idx = jax.lax.broadcasted_iota(jnp.int32, mag.shape, mag.ndim - 1)

    def pos_body(_, carry):
        p_lo, p_hi = carry                             # count(p_lo) < need
        mid = (p_lo + p_hi) >> 1                       # count(p_hi) ≥ need
        cnt = jnp.sum(jnp.logical_and(boundary, idx <= mid)
                      .astype(jnp.int32), axis=-1, keepdims=True)
        enough = cnt >= need
        return jnp.where(enough, p_lo, mid), jnp.where(enough, mid, p_hi)

    _, p = jax.lax.fori_loop(0, d.bit_length(), pos_body,
                             (jnp.full_like(need, -1),
                              jnp.full_like(need, d - 1)))
    take_tie = boundary & (idx <= p) & (need > 0)
    keep = jnp.logical_or(definite, take_tie)
    out_ref[...] = jnp.where(keep, z, jnp.zeros_like(z))


def topk_row(z: jax.Array, k: int, *, bm: int = 8,
             interpret: bool = False) -> jax.Array:
    """Keep k largest-|.| per row of z (rows, d); zero the rest."""
    rows, d = z.shape
    bm = min(bm, rows)
    pm = (-rows) % bm
    if pm:
        z = jnp.pad(z, ((0, pm), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=((rows + pm) // bm,),
        in_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pm, d), z.dtype),
        name="topk_mask",
        interpret=interpret,
    )(z)
    return out[:rows]


__all__ = ["topk_row"]
