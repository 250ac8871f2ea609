"""Pallas TPU kernel: fused flash-decode attention over the serving KV cache.

One query token per row attends its whole cache history in a single fused
program — no materialized dense K/V. Four variants share the kernel body:

- **slot layout**: per-layer cache ``(B, T, Hk, D)`` (B = slots), dense
  floats or INT8 codes + per-head-group f32 scale / uint8 zero dequantized
  IN-TILE;
- **paged layout**: the stacked page pools ``(L, P, page, Hk, D)`` of all
  layers, read as one flat pool ``(L·P, page, Hk, D)`` (a free reshape)
  through a ``(B, n_pages)`` block table offset to the layer's pages —
  each K tile is one page, gathered via the scalar-prefetched table in
  the BlockSpec index map (sentinel entries ``== P`` clip to the layer's
  last page; their garbage is always masked). No per-layer pool is ever
  sliced out.

The kernel is **length-aware**: per-row lengths (scalar-prefetched to SMEM)
bound the K loop. Tiles at or beyond a row's length skip their compute
(``pl.when``) and their index map clamps to the last live tile, so the TPU
pipeline elides the HBM→VMEM copy (same-index revisit) — decode stops
reading dead rows instead of scanning max_len, for dense and INT8 alike.

Softmax is accumulated online (m, l, acc scratch carried across the K-tile
grid axis), f32 statistics, causal mask ``k_idx < length``. Rows with
``length == 0`` produce exactly zero output (the ``l > 0`` guard).

Grid: ``(B, num_k_tiles)`` — K tiles innermost so the scratch carry is
per-row. Head layout matches ``models/layers._scores``: ``H = Hk·g`` with
head ``h`` ↦ ``(h // g, h % g)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dequant_matmul import expand_groups

_NEG_INF = -1e30   # finite init so exp(m_prev - m_new) is 0.0, never NaN


def _cdiv(a, b):
    return (a + b - 1) // b


def _dequant_tile(codes, scale, zero, group: int):
    """In-tile INT8 → f32 expansion; same op order as the reference
    ``kv_cache._reference_dequant`` so fused-vs-reference parity is tight.
    Codes widen through int32 and the per-group planes expand across lanes
    with masks — no (…, D/g, g) reshape, which the TPU compiler refuses for
    groups narrower than 128 lanes."""
    d = codes.shape[-1]
    dg = scale.shape[-1]
    scale = scale.astype(jnp.float32)
    zero = zero.astype(jnp.int32).astype(jnp.float32)
    if dg > 1:
        scale = expand_groups(scale, 0, dg, group, d)
        zero = expand_groups(zero, 0, dg, group, d)
    return (codes.astype(jnp.int32).astype(jnp.float32) - zero) * scale


def _online_update(q, k, v, start, length, bt, sm_scale,
                   m_ref, l_ref, acc_ref):
    """One K tile of online-softmax flash decode. q (H, D) f32; k/v
    (bt, Hk, D) f32; carries (m, l, acc) live in scratch."""
    h, d = q.shape
    hk = k.shape[1]
    g = h // hk
    qh = q.reshape(hk, g, d)
    kt = k.transpose(1, 2, 0)                       # (Hk, D, bt)
    s = jax.lax.dot_general(qh, kt, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    s = s.reshape(h, bt) * sm_scale                 # (H, bt)
    kpos = start + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
    s = jnp.where(kpos < length, s, -jnp.inf)       # causal: k_idx < length
    m_prev = m_ref[:, :1]                           # (H, 1)
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                          # masked cols → exp(-inf)=0
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    vt = v.transpose(1, 0, 2)                       # (Hk, bt, D)
    pv = jax.lax.dot_general(p.reshape(hk, g, bt), vt,
                             (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
    acc_ref[...] = acc_ref[...] * corr + pv.reshape(h, d)


def _make_kernel(*, bt: int, sm_scale: float, group: int, quant: bool,
                 paged: bool):
    def kernel(*refs):
        if paged:
            lens_ref, _table_ref, *rest = refs      # table only feeds maps
        else:
            lens_ref, *rest = refs
        if quant:
            (q_ref, kc_ref, ks_ref, kz_ref, vc_ref, vs_ref, vz_ref,
             o_ref, m_ref, l_ref, acc_ref) = rest
        else:
            q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = rest
        b = pl.program_id(0)
        t = pl.program_id(1)
        nt = pl.num_programs(1)

        @pl.when(t == 0)
        def _init():
            m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        length = lens_ref[b]
        start = t * bt

        @pl.when(start < length)
        def _tile():
            q = q_ref[0].astype(jnp.float32)
            if quant:
                k = _dequant_tile(kc_ref[0], ks_ref[0], kz_ref[0], group)
                v = _dequant_tile(vc_ref[0], vs_ref[0], vz_ref[0], group)
            else:
                k = k_ref[0].astype(jnp.float32)
                v = v_ref[0].astype(jnp.float32)
            _online_update(q, k, v, start, length, bt, sm_scale,
                           m_ref, l_ref, acc_ref)

        @pl.when(t == nt - 1)
        def _emit():
            # l == 0.0 exactly ⇔ no tile ever computed (a length-0 row) →
            # emit zeros. A NaN l (poisoned cache rows) must PROPAGATE so
            # the engine's non-finite decode guard still fails the slot.
            l = l_ref[:, :1]
            dead = l == 0.0
            out = jnp.where(dead, 0.0,
                            acc_ref[...] / jnp.where(dead, 1.0, l))
            o_ref[0] = out.astype(o_ref.dtype)

    return kernel


def flash_decode(q: jax.Array, k, v, lengths: jax.Array, *,
                 k_scale=None, k_zero=None, v_scale=None, v_zero=None,
                 group_size: int = 0, table=None, layer=None,
                 block_t: int = 256, interpret: bool = False) -> jax.Array:
    """Fused flash-decode attention. Returns (B, H, D) in q's dtype.

    q: (B, H, D) — one decode token per row, RoPE already applied.
    lengths: (B,) int32 — row b attends cache positions [0, lengths[b]);
    length 0 → exactly-zero output (a parked slot).

    Slot layout (``table=None``): k/v are (B, T, Hk, D) — dense floats, or
    uint8 codes with (B, T, Hk, D/group) ``*_scale``/``*_zero`` planes.
    Paged layout: k/v are the stacked pools (L, P, page, Hk, D) (same
    quant split), read at layer ``layer`` (an int32 scalar, traced or
    static), or one layer's pool (P, page, Hk, D) with ``layer=None``;
    ``table`` (B, n_pages) int32 maps row positions to physical pages;
    entries == P are sentinels (masked). The stack is read in place as a
    flat (L·P, page, Hk, D) pool with the table offset by ``layer·P``, so
    the kernel and its index maps are those of a single pool. ``block_t``
    tiles the slot K loop (clamped to T); paged tiles are always one page
    wide.
    """
    b, h, d = q.shape
    quant = k_scale is not None
    paged = table is not None
    store = k                 # codes when quant, floats otherwise
    if quant:
        assert group_size > 0 and d % group_size == 0, (d, group_size)
    hk = store.shape[-2]
    assert h % hk == 0, (h, hk)
    sm_scale = 1.0 / math.sqrt(d)
    lengths = lengths.astype(jnp.int32)
    dg = d // group_size if quant else 0

    if paged:
        operands = (k, v) if not quant else (k, k_scale, k_zero,
                                             v, v_scale, v_zero)
        if layer is not None:
            # one flat pool; a sentinel clamps to the layer's last page
            # before the offset, so no other layer's page is ever read
            per_layer = store.shape[1]
            operands = tuple(x.reshape(-1, *x.shape[2:]) for x in operands)
            table = (jnp.minimum(table, per_layer - 1)
                     + jnp.asarray(layer, jnp.int32) * per_layer)
        num_pages, page = operands[0].shape[0], operands[0].shape[1]
        nt = table.shape[1]
        bt = page
        lengths = jnp.minimum(lengths, nt * page)
        grid = (b, nt)

        def kv_map(bi, ti, lens, tbl):
            last = jnp.maximum(_cdiv(lens[bi], page) - 1, 0)
            p = tbl[bi, jnp.minimum(ti, last)]
            return (jnp.minimum(p, num_pages - 1), 0, 0, 0)

        def qo_map(bi, ti, lens, tbl):
            return (bi, 0, 0)

        num_prefetch = 2
        prefetch = (lengths, table.astype(jnp.int32))
        kv_block = (1, page, hk, d)
        sc_block = (1, page, hk, dg)
    else:
        t_len = store.shape[1]
        bt = max(1, min(block_t, t_len))
        pad = (-t_len) % bt
        if pad:
            def pad_t(x, cv=0):
                return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
                               constant_values=cv)
            if quant:
                k, v = pad_t(k), pad_t(v)
                k_scale, v_scale = pad_t(k_scale, 1), pad_t(v_scale, 1)
                k_zero, v_zero = pad_t(k_zero), pad_t(v_zero)
            else:
                k, v = pad_t(k), pad_t(v)
        lengths = jnp.minimum(lengths, t_len)
        grid = (b, (t_len + pad) // bt)

        def kv_map(bi, ti, lens):
            last = jnp.maximum(_cdiv(lens[bi], bt) - 1, 0)
            return (bi, jnp.minimum(ti, last), 0, 0)

        def qo_map(bi, ti, lens):
            return (bi, 0, 0)

        num_prefetch = 1
        prefetch = (lengths,)
        kv_block = (1, bt, hk, d)
        sc_block = (1, bt, hk, dg)
        operands = (k, v) if not quant else (k, k_scale, k_zero,
                                             v, v_scale, v_zero)

    in_specs = [pl.BlockSpec((1, h, d), qo_map)]
    if quant:
        in_specs += [pl.BlockSpec(kv_block, kv_map),
                     pl.BlockSpec(sc_block, kv_map),
                     pl.BlockSpec(sc_block, kv_map)] * 2
    else:
        in_specs += [pl.BlockSpec(kv_block, kv_map)] * 2

    kernel = _make_kernel(bt=bt, sm_scale=sm_scale, group=group_size,
                          quant=quant, paged=paged)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=num_prefetch,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, h, d), qo_map),
            scratch_shapes=[pltpu.VMEM((h, 128), jnp.float32),
                            pltpu.VMEM((h, 128), jnp.float32),
                            pltpu.VMEM((h, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        name="flash_decode",
        interpret=interpret,
    )(*prefetch, q, *operands)


__all__ = ["flash_decode"]
