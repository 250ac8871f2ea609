"""Pallas TPU kernel: fused AWP PGD gradient step  Z = Θ + η·(W−Θ)·C.

The paper's inner-loop hot spot (O(d_out·d_in²) per iteration, §3). TPU
mapping: classic MXU matmul tiling with the subtract folded into the LHS
load and the scale+add epilogue fused into the final K-step — one VMEM
round-trip instead of three separate HLO ops (sub → dot → fma).

Grid (M/bm, N/bn, K/bk); K innermost so the f32 accumulator scratch lives in
VMEM across the contraction. Θ is passed twice with different index maps:
as Θ[i,k] for the residual and Θ[i,j] for the epilogue add.
Block defaults are 128-aligned for the 128×128 MXU.

Batched form: ``(B, M, K)`` weights with ``(B, K, K)`` covariances and a
per-item η run on a ``(B, M/bm, N/bn, K/bk)`` grid — one device program for a
whole shape bucket (all q/k/v heads, every MoE expert of a block), which is
what the batched compression engine in ``repro.core.batched`` drives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NRM_ROWS = 8      # sublane height of the per-block residual-norm tiles


def _kernel(w_ref, theta_k_ref, c_ref, theta_out_ref, eta_ref, z_ref, nrm_ref,
            acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    resid = (w_ref[...] - theta_k_ref[...]).astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(
        resid, c_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        eta = eta_ref[0, 0]
        acc = acc_ref[...]
        z_ref[...] = (theta_out_ref[...].astype(jnp.float32)
                      + eta * acc).astype(z_ref.dtype)
        # per-block ‖(W−Θ)C‖² column partials from the f32 accumulator —
        # recovering the residual norm from Z−Θ instead would cancel
        # catastrophically near convergence (‖ηR‖ ≪ ‖Θ‖) and floor the PGD
        # stopping rule. Stored lane-dense: an (8, bn) tile repeating the
        # (1, bn) partial, so the block stays (8, 128)-aligned.
        part = jnp.sum(acc * acc, axis=0, keepdims=True)
        nrm_ref[...] = jnp.broadcast_to(part, nrm_ref.shape)


def _kernel_batched(w_ref, theta_k_ref, c_ref, theta_out_ref, eta_ref, z_ref,
                    nrm_ref, acc_ref, *, n_k: int):
    """Batched variant: blocks carry a leading singleton batch dim; K is
    grid axis 3. η is per-item, read from the (B, 1) SMEM array."""
    item = pl.program_id(0)

    @pl.when(pl.program_id(3) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    resid = (w_ref[0] - theta_k_ref[0]).astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(
        resid, c_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == n_k - 1)
    def _epilogue():
        eta = eta_ref[item, 0]
        acc = acc_ref[...]
        z_ref[0] = (theta_out_ref[0].astype(jnp.float32)
                    + eta * acc).astype(z_ref.dtype)
        part = jnp.sum(acc * acc, axis=0, keepdims=True)
        nrm_ref[0] = jnp.broadcast_to(part, nrm_ref.shape[1:])


def _step_batched(w, theta, c, eta, *, bm, bn, bk, interpret,
                  with_resid_norm):
    """(B, M, K) × (B, K, K) batched step; eta scalar or (B,)."""
    b, m, k = w.shape
    assert theta.shape == (b, m, k) and c.shape == (b, k, k)
    bm, bn, bk = min(bm, m), min(bn, k), min(bk, k)
    pm, pn, pk = (-m) % bm, (-k) % bn, (-k) % bk
    if pm or pk:
        w = jnp.pad(w, ((0, 0), (0, pm), (0, pk)))
        theta = jnp.pad(theta, ((0, 0), (0, pm), (0, pk)))
    if pk or pn:
        c = jnp.pad(c, ((0, 0), (0, pk), (0, pn)))
    mp, kp, np_ = m + pm, k + pk, k + pn
    n_k = kp // bk
    gm, gn = mp // bm, np_ // bn
    eta_arr = jnp.broadcast_to(
        jnp.asarray(eta, jnp.float32).reshape(-1), (b,)).reshape(b, 1)

    grid = (b, gm, gn, n_k)
    out, nrm = pl.pallas_call(
        functools.partial(_kernel_batched, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda bb, i, j, kk: (bb, i, kk)),  # W
            pl.BlockSpec((1, bm, bk), lambda bb, i, j, kk: (bb, i, kk)),  # Θ[i,k]
            pl.BlockSpec((1, bk, bn), lambda bb, i, j, kk: (bb, kk, j)),  # C
            pl.BlockSpec((1, bm, bn), lambda bb, i, j, kk: (bb, i, j)),   # Θ[i,j]
            pl.BlockSpec(memory_space=pltpu.SMEM),                        # η
        ],
        out_specs=[
            pl.BlockSpec((1, bm, bn), lambda bb, i, j, kk: (bb, i, j)),
            pl.BlockSpec((1, _NRM_ROWS, bn), lambda bb, i, j, kk: (bb, i, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, mp, np_), w.dtype),
                   jax.ShapeDtypeStruct((b, gm * _NRM_ROWS, np_),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="awp_pgd",
        interpret=interpret,
    )(w, theta, c, theta, eta_arr)
    z = out[:, :m, :k]
    if not with_resid_norm:
        return z
    return z, jnp.sqrt(nrm[:, ::_NRM_ROWS].sum(axis=(-2, -1)))


def awp_pgd_step(w: jax.Array, theta: jax.Array, c: jax.Array, eta,
                 *, bm: int = 128, bn: int = 128, bk: int = 128,
                 interpret: bool = False, with_resid_norm: bool = False):
    """One PGD gradient step (no projection). w, theta: (M, K); c: (K, N=K).

    3-D inputs ``(B, M, K)`` / ``(B, K, K)`` run the batched grid (η may then
    be per-item, shape ``(B,)``). ``with_resid_norm=True`` additionally
    returns ‖(W−Θ)C‖_F (per item when batched), summed exactly from the f32
    accumulator blocks — the PGD stopping rule consumes this instead of
    reconstructing it from Z−Θ, which cancels near convergence."""
    if w.ndim == 3:
        return _step_batched(w, theta, c, eta, bm=bm, bn=bn, bk=bk,
                             interpret=interpret,
                             with_resid_norm=with_resid_norm)
    m, k = w.shape
    k2, n = c.shape
    assert k == k2 and theta.shape == (m, k) and n == k
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        w = jnp.pad(w, ((0, pm), (0, pk)))
        theta = jnp.pad(theta, ((0, pm), (0, pk)))
    if pk or pn:
        c = jnp.pad(c, ((0, pk), (0, pn)))
    mp, kp, np_ = m + pm, k + pk, n + pn
    n_k = kp // bk
    gm, gn = mp // bm, np_ // bn
    eta_arr = jnp.full((1, 1), eta, jnp.float32)

    grid = (gm, gn, n_k)
    out, nrm = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),   # W[i, k]
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),   # Θ[i, k]
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),   # C[k, j]
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),    # Θ[i, j]
            pl.BlockSpec(memory_space=pltpu.SMEM),              # η
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((_NRM_ROWS, bn), lambda i, j, kk: (i, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((mp, np_), w.dtype),
                   jax.ShapeDtypeStruct((gm * _NRM_ROWS, np_), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="awp_pgd",
        interpret=interpret,
    )(w, theta, c, theta, eta_arr)
    z = out[:m, :n]
    if not with_resid_norm:
        return z
    return z, jnp.sqrt(nrm[::_NRM_ROWS].sum())


__all__ = ["awp_pgd_step"]
