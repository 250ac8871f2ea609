"""Pallas TPU kernel: fused flash-decode attention over the serving KV cache.

One query token per row attends its whole cache history in a single fused
program — no materialized dense K/V. Four variants share the online
softmax, the in-tile INT8 dequant and the emit guard:

- **slot layout**: per-layer cache ``(B, T, Hk, D)`` (B = slots), dense
  floats or INT8 codes + per-head-group f32 scale / uint8 zero dequantized
  IN-TILE.
- **paged layout**: the stacked page pools ``(L, P, page, Hk, D)`` of all
  layers, read as one flat pool ``(L·P, page, Hk, D)`` (a free reshape)
  through a ``(B, n_pages)`` block table offset to the layer's pages.
  Sentinel entries ``== P`` clip to the layer's last page before the
  ``layer·P`` offset, so no read leaves the layer, and no per-layer pool
  is ever sliced out.

Paged pools of 32-bit floats whose head_dim is whole 128-lane tiles run
the **paged loop**: grid ``(B,)``, the pools left in HBM. Each row loops
over its live pages only, in blocks of :func:`pages_per_block` pages
(about 256 tokens, from the shapes), copied page by page through the
scalar-prefetched table into a double-buffered VMEM block; the next block
(or the next live row's first) is in flight while this one is computed,
one KV head at a time. No page at or past a row's length is copied and a
length-0 row copies nothing.

Everything else runs the **tiled grid** ``(B, num_k_tiles)``, K tiles
innermost, each fetched by its BlockSpec: ``block_t`` tokens of a slot
cache, or one page gathered through the table in the index map (Mosaic
slices an HBM ref only along a minor dim of whole 128-lane tiles, so INT8
planes and narrow heads cannot be copied by hand). A tile at or beyond a
row's length skips its compute (``pl.when``) and its index map clamps to
the last live tile, so the pipeline elides the copy (same-index revisit).

Softmax is accumulated online (m, l, acc scratch), f32 statistics, causal
mask ``k_idx < length``. Rows with ``length == 0`` produce exactly zero
output (the ``l > 0`` guard). Head layout matches
``models/layers._scores``: ``H = Hk·g`` with head ``h`` ↦ ``(h // g, h % g)``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dequant_matmul import expand_groups

_NEG_INF = -1e30   # finite init so exp(m_prev - m_new) is 0.0, never NaN
_PAGED_BLOCK_TOKENS = 256      # tokens a paged K/V block aims for
_PAGED_VMEM_BUDGET = 8 << 20   # bytes of the double-buffered paged blocks


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


def _kv_tile(planes, group: int):
    """K or V of one tile as f32: the dense plane, or (codes, scale, zero)
    dequantized in-tile."""
    if len(planes) == 3:
        return _dequant_tile(*planes, group)
    return planes[0].astype(jnp.float32)


def _accumulate(s, start, length, rows, m_ref, l_ref, acc_ref, pv):
    """One K tile of online-softmax flash decode for the heads ``rows`` of
    the carries (m, l, acc in scratch): s (r, bt) f32 scaled scores of the
    tile's keys from position ``start``; ``pv(p)`` → (r, D), p · V."""
    bt = s.shape[-1]
    kpos = start + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
    s = jnp.where(kpos < length, s, -jnp.inf)       # causal: k_idx < length
    m_prev = m_ref[rows, :1]                        # (r, 1)
    l_prev = l_ref[rows, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                          # masked cols → exp(-inf)=0
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    r = s.shape[0]
    m_ref[rows, :] = jnp.broadcast_to(m_new, (r, m_ref.shape[1]))
    l_ref[rows, :] = jnp.broadcast_to(l_new, (r, l_ref.shape[1]))
    acc_ref[rows, :] = acc_ref[rows, :] * corr + pv(p)


def _online_update(q, k, v, start, length, sm_scale, m_ref, l_ref, acc_ref):
    """All heads at once: q (H, D) f32; k/v (bt, Hk, D) f32 values."""
    h, d = q.shape
    bt, hk = k.shape[0], k.shape[1]
    g = h // hk
    kt = k.transpose(1, 2, 0)                       # (Hk, D, bt)
    s = jax.lax.dot_general(q.reshape(hk, g, d), kt,
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)

    def pv(p):
        vt = v.transpose(1, 0, 2)                   # (Hk, bt, D)
        return jax.lax.dot_general(p.reshape(hk, g, bt), vt,
                                   (((2,), (1,)), ((0,), (0,))),
                                   preferred_element_type=jnp.float32
                                   ).reshape(h, d)

    _accumulate(s.reshape(h, bt) * sm_scale, start, length, slice(None),
                m_ref, l_ref, acc_ref, pv)


def _online_update_heads(q_ref, k_ref, v_ref, start, length, sm_scale,
                         m_ref, l_ref, acc_ref):
    """One KV head at a time: q_ref (1, H, D); k_ref/v_ref (bt, Hk, D) VMEM
    refs, each head's (bt, D) read by a strided load — no transposed copy
    of the tile."""
    hk = k_ref.shape[1]
    g = q_ref.shape[1] // hk
    for j in range(hk):
        rows = pl.ds(j * g, g)
        q = q_ref[0, rows, :].astype(jnp.float32)   # (g, D)
        k = k_ref[:, j, :].astype(jnp.float32)      # (bt, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        _accumulate(s * sm_scale, start, length, rows, m_ref, l_ref, acc_ref,
                    lambda p, j=j: jnp.dot(
                        p, v_ref[:, j, :].astype(jnp.float32),
                        preferred_element_type=jnp.float32))


def _init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)


def _emit(o_ref, l_ref, acc_ref):
    # l == 0.0 exactly ⇔ no tile ever computed (a length-0 row) → emit
    # zeros. A NaN l (poisoned cache rows) must PROPAGATE so the engine's
    # non-finite decode guard still fails the slot.
    l = l_ref[:, :1]
    dead = l == 0.0
    out = jnp.where(dead, 0.0, acc_ref[...] / jnp.where(dead, 1.0, l))
    o_ref[0] = out.astype(o_ref.dtype)


def _make_tiled_kernel(*, bt: int, sm_scale: float, group: int, n_kv: int,
                       n_prefetch: int):
    """Grid (B, K tiles): each tile fetched by its BlockSpec."""
    def kernel(*refs):
        lens_ref = refs[0]
        q_ref, *rest = refs[n_prefetch:]
        kv_refs = rest[:n_kv]
        o_ref, m_ref, l_ref, acc_ref = rest[n_kv:]
        b = pl.program_id(0)
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _():
            _init(m_ref, l_ref, acc_ref)

        length = lens_ref[b]
        start = t * bt

        @pl.when(start < length)
        def _tile():
            q = q_ref[0].astype(jnp.float32)
            half = n_kv // 2
            k = _kv_tile([r[0] for r in kv_refs[:half]], group)
            v = _kv_tile([r[0] for r in kv_refs[half:]], group)
            _online_update(q, k, v, start, length, sm_scale,
                           m_ref, l_ref, acc_ref)

        @pl.when(t == pl.num_programs(1) - 1)
        def _():
            _emit(o_ref, l_ref, acc_ref)

    return kernel


def _copies_by_page(x) -> bool:
    """Whether the loop can copy a plane page by page and read it head by
    head: Mosaic slices an HBM ref only along a minor dim of whole 128-lane
    tiles, and loads a head's rows strided only from 32-bit types."""
    return x.shape[-1] % 128 == 0 and jnp.dtype(x.dtype).itemsize == 4


def pages_per_block(page: int, n_pages: int, kv_heads: int,
                    head_dim: int) -> int:
    """Pages in one block of the paged loop: about ``_PAGED_BLOCK_TOKENS``
    tokens, halved until the double-buffered K and V blocks (32-bit, the
    heads padded to 8 sublanes) fit ``_PAGED_VMEM_BUDGET``; at least one
    page and at most the table."""
    per_token = 2 * 2 * _cdiv(kv_heads, 8) * 8 * head_dim * 4
    ppb = max(1, _PAGED_BLOCK_TOKENS // page)
    while ppb > 1 and ppb * page * per_token > _PAGED_VMEM_BUDGET:
        ppb //= 2
    return max(1, min(ppb, n_pages))


def _paged_loop(q, pools, lengths, table, *, page: int, sm_scale: float,
                scratch, interpret: bool):
    """Grid (B,): row b loops over its live pages, ``ppb`` to a block.
    pools: the flat K and V pools (N, page, Hk, D) left in HBM; table
    (B, n_pages) already clamped and offset into them."""
    b, h, d = q.shape
    hk = pools[0].shape[-2]
    ppb = pages_per_block(page, table.shape[1], hk, d)
    bt = ppb * page

    def kernel(lens_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
               sems, slot_ref, m_ref, l_ref, acc_ref):
        row = pl.program_id(0)

        def live_pages(r):
            return _cdiv(lens_ref[r], page)

        def live_blocks(r):
            # [first, end): a window would raise ``first``
            return 0, _cdiv(live_pages(r), ppb)

        def copy_block(r, blk, slot, wait: bool):
            """Start (or wait for) the copies of row ``r``'s live pages in
            block ``blk`` into ``slot``; no page past the row's length."""
            lo = blk * ppb

            def one(j, carry):
                p = table_ref[r, j]
                dst = pl.ds(pl.multiple_of((j - lo) * page, page), page)
                for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    cp = pltpu.make_async_copy(hbm.at[p], buf.at[slot, dst],
                                               sems.at[slot])
                    if wait:
                        cp.wait()
                    else:
                        cp.start()
                return carry

            jax.lax.fori_loop(lo, jnp.minimum(lo + ppb, live_pages(r)),
                              one, 0)

        @pl.when(row == 0)
        def _():
            slot_ref[0] = 0

        slot0 = slot_ref[0]              # the slot of this row's first block
        first, end = live_blocks(row)
        nxt = jnp.minimum(row + 1, b - 1)
        next_live = (row + 1 < b) & (lens_ref[nxt] > 0)

        # A live row's first block was started by the grid step before
        # (row 0's, here); a dead row starts the next row's at once.
        @pl.when((row == 0) & (end > first))
        def _():
            copy_block(row, first, slot0, wait=False)

        @pl.when((end <= first) & next_live)
        def _():
            copy_block(nxt, live_blocks(nxt)[0], slot0, wait=False)

        _init(m_ref, l_ref, acc_ref)
        length = lens_ref[row]

        def step(i, carry):
            slot = (slot0 + i - first) % 2

            @pl.when(i + 1 < end)
            def _():
                copy_block(row, i + 1, 1 - slot, wait=False)

            @pl.when((i + 1 == end) & next_live)
            def _():
                copy_block(nxt, live_blocks(nxt)[0], 1 - slot, wait=False)

            copy_block(row, i, slot, wait=True)

            # V pages past the row's length hold stale VMEM (a NaN there
            # would survive p = 0): zero them
            def clear(j, c):
                dst = pl.ds(pl.multiple_of(j * page, page), page)
                v_buf[slot, dst] = jnp.zeros((page, hk, d), v_buf.dtype)
                return c

            jax.lax.fori_loop(live_pages(row) - i * ppb, ppb, clear, 0)
            _online_update_heads(q_ref, k_buf.at[slot], v_buf.at[slot],
                                 i * bt, length, sm_scale,
                                 m_ref, l_ref, acc_ref)
            return carry

        jax.lax.fori_loop(first, end, step, 0)
        slot_ref[0] = (slot0 + end - first) % 2
        _emit(o_ref, l_ref, acc_ref)

    row_map = lambda r, lens, tbl: (r, 0, 0)  # noqa: E731
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, h, d), row_map),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, h, d), row_map),
            scratch_shapes=[pltpu.VMEM((2, bt, hk, d), x.dtype) for x in pools]
            + [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)]
            + scratch,
        ),
        # a row starts its successor's first copy: the rows run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        name="flash_decode",
        interpret=interpret,
    )(lengths, table, q, *pools)


def flash_decode(q: jax.Array, k, v, lengths: jax.Array, *,
                 k_scale=None, k_zero=None, v_scale=None, v_zero=None,
                 group_size: int = 0, table=None, layer=None,
                 block_t: int = 256, interpret: bool = False) -> jax.Array:
    """Fused flash-decode attention. Returns (B, H, D) in q's dtype.

    q: (B, H, D) — one decode token per row, RoPE already applied.
    lengths: (B,) int32 — row b attends cache positions [0, lengths[b]);
    length 0 → exactly-zero output (a parked slot).

    Slot layout (``table=None``): k/v are (B, T, Hk, D) — dense floats, or
    uint8 codes with (B, T, Hk, D/group) ``*_scale``/``*_zero`` planes;
    ``block_t`` tiles the K loop (clamped to T).
    Paged layout: k/v are the stacked pools (L, P, page, Hk, D) (same
    quant split), read at layer ``layer`` (an int32 scalar, traced or
    static), or one layer's pool (P, page, Hk, D) with ``layer=None``;
    ``table`` (B, n_pages) int32 maps row positions to physical pages;
    entries == P are sentinels (masked). The stack is read in place as a
    flat (L·P, page, Hk, D) pool with the table offset by ``layer·P``.
    f32 pools with D a multiple of 128 run the paged loop (each row copies
    its live pages only, ``pages_per_block`` pages to a block); others
    tile the K loop one page a grid step. ``block_t`` plays no part.
    """
    b, h, d = q.shape
    quant = k_scale is not None
    if quant:
        assert group_size > 0 and d % group_size == 0, (d, group_size)
    hk = k.shape[-2]
    assert h % hk == 0, (h, hk)
    sm_scale = 1.0 / math.sqrt(d)
    lengths = lengths.astype(jnp.int32)
    operands = (k, v) if not quant else (k, k_scale, k_zero,
                                         v, v_scale, v_zero)
    n_kv = len(operands)
    scratch = [pltpu.VMEM((h, 128), jnp.float32),
               pltpu.VMEM((h, 128), jnp.float32),
               pltpu.VMEM((h, d), jnp.float32)]

    if table is not None:
        per_layer = k.shape[-4]
        offset = 0
        if layer is not None:
            # one flat pool; a sentinel clamps to the layer's last page
            # before the offset, so no other layer's page is ever read
            operands = tuple(x.reshape(-1, *x.shape[2:]) for x in operands)
            offset = jnp.asarray(layer, jnp.int32) * per_layer
        table = jnp.minimum(table.astype(jnp.int32), per_layer - 1) + offset
        page, n_pages = k.shape[-3], table.shape[1]
        lengths = jnp.minimum(lengths, n_pages * page)
        if all(_copies_by_page(x) for x in operands):
            return _paged_loop(q, operands, lengths, table, page=page,
                               sm_scale=sm_scale, scratch=scratch,
                               interpret=interpret)
        bt = page

        def kv_map(bi, ti, lens, tbl):
            last = jnp.maximum(_cdiv(lens[bi], page) - 1, 0)
            return (tbl[bi, jnp.minimum(ti, last)], 0, 0, 0)

        grid = (b, n_pages)
        prefetch = (lengths, table)
    else:
        t_len = k.shape[1]
        bt = max(1, min(block_t, t_len))
        pad = (-t_len) % bt
        if pad:
            operands = tuple(
                jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
                        constant_values=1 if i in (1, 4) and quant else 0)
                for i, x in enumerate(operands))
        lengths = jnp.minimum(lengths, t_len)

        def kv_map(bi, ti, lens):
            last = jnp.maximum(_cdiv(lens[bi], bt) - 1, 0)
            return (bi, jnp.minimum(ti, last), 0, 0)

        grid = (b, (t_len + pad) // bt)
        prefetch = (lengths,)

    def qo_map(bi, ti, *_):
        return (bi, 0, 0)

    return pl.pallas_call(
        _make_tiled_kernel(bt=bt, sm_scale=sm_scale, group=group_size,
                           n_kv=n_kv, n_prefetch=len(prefetch)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[pl.BlockSpec((1, h, d), qo_map)]
            + [pl.BlockSpec((1, bt) + x.shape[2:], kv_map)
               for x in operands],
            out_specs=pl.BlockSpec((1, h, d), qo_map),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        name="flash_decode",
        interpret=interpret,
    )(*prefetch, q, *operands)


__all__ = ["flash_decode", "pages_per_block"]
