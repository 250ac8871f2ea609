"""jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in interpret mode; on TPU they
compile natively. ``use_pallas=False`` falls back to the jnp oracle — the
switch the perf harness flips when comparing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import awp_pgd as _awp_pgd
from repro.kernels import topk_mask as _topk
from repro.kernels import quant_proj as _quant
from repro.kernels import dequant_matmul as _dq
from repro.kernels import kv_dequant as _kv
from repro.kernels import decode_attn as _da
from repro.kernels import ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def awp_pgd_step(w, theta, c, eta, use_pallas: bool = True):
    """Fused Z = Θ + η(W−Θ)C. Accepts (M, K) or batched (B, M, K) operands
    (per-item η allowed in the batched form — one program per shape bucket)."""
    if not use_pallas:
        return ref.awp_pgd_step(w, theta, c, eta)
    return _awp_pgd.awp_pgd_step(w, theta, c, eta, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("k", "use_pallas"))
def topk_row(z, k: int, use_pallas: bool = True):
    if not use_pallas:
        return ref.topk_row(z, k)
    return _topk.topk_row(z, k, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("bits", "group_size", "use_pallas"))
def quant_project(z, bits: int, group_size: int = 128, use_pallas: bool = True):
    if not use_pallas:
        return ref.quant_project(z, bits, group_size)
    return _quant.quant_project(z, bits, group_size, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("group_size", "use_pallas"))
def dequant_matmul(x, packed, scale, zero, group_size: int = 128,
                   use_pallas: bool = True):
    if not use_pallas:
        return ref.dequant_matmul(x, packed, scale, zero, group_size)
    return _dq.dequant_matmul(x, packed, scale, zero, group_size=group_size,
                              interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("group_size", "use_pallas"))
def kv_dequant(codes, scale, zero, group_size: int, use_pallas: bool = True):
    """INT8 KV-cache expansion: codes (R, K) uint8 + per-group scale/zero →
    (R, K) f32 (the attention-read side of the quantized slot cache)."""
    if not use_pallas:
        return ref.kv_dequant(codes, scale, zero, group_size)
    return _kv.kv_dequant(codes, scale, zero, group_size=group_size,
                          interpret=_interpret())


def _ref_dequant_kv(codes, scale, zero, group_size: int):
    """(B, T, Hk, D) codes + (B, T, Hk, D/g) planes → dense f32, via the
    flattened-row reference expansion (the dequant-then-attend oracle)."""
    lead = codes.shape[:-1]
    rows = 1
    for s in lead:
        rows *= s
    flat = ref.kv_dequant(codes.reshape(rows, codes.shape[-1]),
                          scale.reshape(rows, -1), zero.reshape(rows, -1),
                          group_size)
    return flat.reshape(codes.shape)


@functools.partial(jax.jit, static_argnames=("group_size", "block_t",
                                             "use_pallas"))
def decode_attn(q, k, v, lengths, k_scale=None, k_zero=None, v_scale=None,
                v_zero=None, group_size: int = 0, block_t: int = 256,
                use_pallas: bool = True):
    """Fused flash-decode over a slot-layout cache: q (B, H, D) one token
    per row against k/v (B, T, Hk, D) — dense floats, or uint8 codes with
    per-head-group scale/zero planes dequantized in-tile. ``lengths`` (B,)
    bounds each row's K loop (length 0 → zero output). The jnp oracle
    (``use_pallas=False``) dequantizes then attends — the pre-fusion
    reference path."""
    if not use_pallas:
        if k_scale is not None:
            k = _ref_dequant_kv(k, k_scale, k_zero, group_size)
            v = _ref_dequant_kv(v, v_scale, v_zero, group_size)
        return ref.decode_attn(q, k, v, lengths)
    return _da.flash_decode(q, k, v, lengths, k_scale=k_scale, k_zero=k_zero,
                            v_scale=v_scale, v_zero=v_zero,
                            group_size=group_size, block_t=block_t,
                            interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("group_size", "use_pallas"))
def decode_attn_paged(q, k, v, table, lengths, k_scale=None, k_zero=None,
                      v_scale=None, v_zero=None, group_size: int = 0,
                      layer=None, use_pallas: bool = True):
    """Paged fused flash-decode: k/v are the stacked page pools
    (L, P, page, Hk, D) read at ``layer`` (int32 scalar), or one layer's
    pool (P, page, Hk, D) with ``layer=None`` (same dense/INT8 split as
    :func:`decode_attn`), and ``table`` (B, n_pages) int32 routes each
    row's positions to physical pages — gathered tile-by-tile in the
    kernel's index maps, sentinel entries (== P) masked. The oracle takes
    the layer's pool, gathers the contiguous view, then dequantizes and
    attends."""
    if not use_pallas:
        if layer is not None:
            k, v = k[layer], v[layer]
            if k_scale is not None:
                k_scale, k_zero = k_scale[layer], k_zero[layer]
                v_scale, v_zero = v_scale[layer], v_zero[layer]
        if k_scale is not None:
            k = _ref_dequant_kv(k, k_scale, k_zero, group_size)
            v = _ref_dequant_kv(v, v_scale, v_zero, group_size)
        return ref.decode_attn_paged(q, k, v, table, lengths)
    return _da.flash_decode(q, k, v, lengths, k_scale=k_scale, k_zero=k_zero,
                            v_scale=v_scale, v_zero=v_zero,
                            group_size=group_size, table=table, layer=layer,
                            interpret=_interpret())


def awp_prune_fused(w, c, k: int, eta, iters: int, theta0=None,
                    use_pallas: bool = True):
    """Full AWP pruning loop on the kernel path: fused PGD step + bisection
    top-k per iteration (the production compression inner loop)."""
    theta = w if theta0 is None else theta0
    def body(theta, _):
        z = awp_pgd_step(w, theta, c, eta, use_pallas=use_pallas)
        return topk_row(z, k, use_pallas=use_pallas), None
    theta, _ = jax.lax.scan(body, theta, None, length=iters)
    return theta


__all__ = ["awp_pgd_step", "topk_row", "quant_project", "dequant_matmul",
           "kv_dequant", "decode_attn", "decode_attn_paged",
           "awp_prune_fused"]
