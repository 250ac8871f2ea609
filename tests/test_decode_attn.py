"""Fused flash-decode attention kernel: parity matrix vs the reference
gather-then-attend path over paged pools, dense/INT8 storage, head-group
sizes, page sizes and ragged per-slot lengths (length-0 and full-cache
slots included) — plus the engine-level greedy bit-parity contract for the
``use_fused_decode`` escape hatch."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny_config
from repro.kernels import decode_attn, ops, ref as kref
from repro.models import build_model
from repro.models.layers import decode_attention
from repro.serving import Engine, EngineConfig, GenerationRequest, \
    SamplingParams
from repro.serving.kv_cache import QuantizedKV, fused_decode_attn, \
    kv_quantize

D = 16
T = 40
LENS = [0, 1, 17, 23, 40]        # parked, single-token, ragged, full cache


def _qkv(rng, b, t, hk, g, dtype=jnp.float32, d=D):
    h = hk * g
    q = jnp.asarray(rng.normal(size=(b, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, t, hk, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, t, hk, d)), dtype)
    return q, k, v


def _paged_pool(rng, k, v, lens, page):
    """Scatter contiguous (B, T, Hk, D) rows into a shuffled page pool;
    unused table entries carry the sentinel (== num_pages)."""
    b, t = k.shape[0], k.shape[1]
    npg = -(-t // page)
    num_pages = b * npg + 3                     # spare pages stay garbage
    pool_k = np.asarray(rng.normal(size=(num_pages, page) + k.shape[2:]),
                        np.float32)
    pool_v = np.asarray(rng.normal(size=pool_k.shape), np.float32)
    table = np.full((b, npg), num_pages, np.int32)
    order = rng.permutation(num_pages)
    nxt = 0
    for row in range(b):
        for c in range(-(-int(lens[row]) // page)):
            p = int(order[nxt]); nxt += 1
            table[row, c] = p
            n = min(page, int(lens[row]) - c * page)
            pool_k[p, :n] = np.asarray(k[row, c * page:c * page + n])
            pool_v[p, :n] = np.asarray(v[row, c * page:c * page + n])
    return (jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(table),
            num_pages)


# ---------------------------------------------------------------------------
# paged pools: ragged lengths, head groups, page sizes, INT8 groups
# ---------------------------------------------------------------------------

def _paged_case(rng, hk, g, page, dtype=jnp.float32):
    """q, the contiguous rows, and their shuffled page pool at LENS."""
    q, k, v = _qkv(rng, len(LENS), T, hk, g, dtype)
    pool_k, pool_v, table, _ = _paged_pool(rng, k, v, LENS, page)
    return q, k, v, pool_k.astype(dtype), pool_v.astype(dtype), table


@pytest.mark.parametrize("hk,g", [(1, 1), (2, 1), (2, 4)])
def test_paged_dense_parity_ragged_lengths(hk, g):
    rng = np.random.default_rng(0)
    q, k, v, pool_k, pool_v, table = _paged_case(rng, hk, g, 8)
    lens = jnp.asarray(LENS, jnp.int32)
    ref = ops.decode_attn_paged(q, pool_k, pool_v, table, lens,
                                use_pallas=False)
    fused = ops.decode_attn_paged(q, pool_k, pool_v, table, lens)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    assert np.all(np.asarray(fused[0]) == 0.0)       # length-0 → exact zeros
    # the reference path itself must agree with decode_attention (the
    # model's masked-softmax read) over the contiguous rows on live rows
    live = [i for i, l in enumerate(LENS) if l > 0]
    pos = (lens - 1)[jnp.asarray(live)][:, None]
    da = decode_attention(q[jnp.asarray(live)][:, None], k[jnp.asarray(live)],
                          v[jnp.asarray(live)], pos)[:, 0]
    np.testing.assert_allclose(np.asarray(ref)[live], np.asarray(da),
                               atol=1e-5, rtol=1e-5)


def test_paged_single_page_is_bit_identical():
    """With one page covering the whole cache the online softmax visits
    every key in one pass — fused output is bit-identical to the oracle."""
    rng = np.random.default_rng(1)
    q, _, _, pool_k, pool_v, table = _paged_case(rng, 2, 2, T)
    lens = jnp.asarray(LENS, jnp.int32)
    ref = ops.decode_attn_paged(q, pool_k, pool_v, table, lens,
                                use_pallas=False)
    fused = ops.decode_attn_paged(q, pool_k, pool_v, table, lens)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))


def test_paged_page_size_invariance():
    """The same rows in pools of 7-, 16-, 40- and 64-token pages (a
    partial last page, several pages, one page, a page wider than the
    cache) attend to the same output."""
    lens = jnp.asarray(LENS, jnp.int32)
    outs = []
    for page in (7, 16, 40, 64):
        rng = np.random.default_rng(2)
        q, _, _, pool_k, pool_v, table = _paged_case(rng, 2, 2, page)
        outs.append(ops.decode_attn_paged(q, pool_k, pool_v, table, lens))
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("group", [D, D // 2])
def test_paged_int8_parity_per_group(group):
    rng = np.random.default_rng(3)
    q, _, _, pool_k, pool_v, table = _paged_case(rng, 2, 3, 8)
    qk, qv = kv_quantize(pool_k, group), kv_quantize(pool_v, group)
    lens = jnp.asarray(LENS, jnp.int32)
    args = (q, qk.codes, qv.codes, table, lens, qk.scale, qk.zero, qv.scale,
            qv.zero)
    ref = ops.decode_attn_paged(*args, group_size=group, use_pallas=False)
    fused = ops.decode_attn_paged(*args, group_size=group)
    # same in-tile dequant numerics as the reference expansion → float-tight
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    assert np.all(np.asarray(fused[0]) == 0.0)


def test_paged_dense_parity_and_slot_equivalence():
    rng = np.random.default_rng(4)
    q, k, v = _qkv(rng, len(LENS), T, 2, 2)
    lens = jnp.asarray(LENS, jnp.int32)
    pool_k, pool_v, table, _ = _paged_pool(rng, k, v, LENS, page=8)
    ref = ops.decode_attn_paged(q, pool_k, pool_v, table, lens,
                                use_pallas=False)
    fused = ops.decode_attn_paged(q, pool_k, pool_v, table, lens)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    # the paged read attends the same written tokens as a contiguous read
    rows = kref.decode_attn(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(rows),
                               atol=1e-5, rtol=1e-5)
    assert np.all(np.asarray(fused[0]) == 0.0)       # all-sentinel row


def test_paged_int8_parity():
    rng = np.random.default_rng(5)
    group = D // 2
    q, k, v = _qkv(rng, len(LENS), T, 2, 2)
    lens = jnp.asarray(LENS, jnp.int32)
    page = 8
    pool_k, pool_v, table, _ = _paged_pool(rng, k, v, LENS, page=page)
    qk, qv = kv_quantize(pool_k, group), kv_quantize(pool_v, group)
    args = (q, qk.codes, qv.codes, table, lens, qk.scale, qk.zero,
            qv.scale, qv.zero)
    ref = ops.decode_attn_paged(*args, group_size=group, use_pallas=False)
    fused = ops.decode_attn_paged(*args, group_size=group)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


LAYERS = 3


@pytest.mark.parametrize("layer", [0, 1, LAYERS - 1])
@pytest.mark.parametrize("int8", [False, True], ids=["dense", "int8"])
def test_paged_stacked_pool_reads_layer_in_place(layer, int8):
    """The stacked pools (L, P, page, Hk, D) read at a traced layer index
    give bit for bit what the same kernel gives on that layer's pool alone
    — sentinel table entries and the length-0 row included — and the other
    layers' pages are never read."""
    rng = np.random.default_rng(8)
    q, k, v = _qkv(rng, len(LENS), T, 2, 2)
    lens = jnp.asarray(LENS, jnp.int32)
    pool_k, pool_v, table, _ = _paged_pool(rng, k, v, LENS, page=8)
    assert int(table[0, 0]) == pool_k.shape[0]       # length-0 row: sentinel
    others = [jnp.asarray(rng.normal(size=pool_k.shape), jnp.float32)
              for _ in range(LAYERS)]

    def stack(pool):
        return jnp.stack([pool if i == layer else others[i]
                          for i in range(LAYERS)])

    if int8:
        group = D // 2
        planes = [kv_quantize(p, group) for p in (pool_k, pool_v)]
        stacked = [kv_quantize(stack(p), group) for p in (pool_k, pool_v)]
        args = lambda kq, vq: (kq.codes, vq.codes, table, lens, kq.scale,
                               kq.zero, vq.scale, vq.zero)
        one = ops.decode_attn_paged(q, *args(*planes), group_size=group)
        got = ops.decode_attn_paged(q, *args(*stacked), group_size=group,
                                    layer=jnp.int32(layer))
        ref = ops.decode_attn_paged(q, *args(*stacked), group_size=group,
                                    layer=jnp.int32(layer), use_pallas=False)
    else:
        one = ops.decode_attn_paged(q, pool_k, pool_v, table, lens)
        got = ops.decode_attn_paged(q, stack(pool_k), stack(pool_v), table,
                                    lens, layer=jnp.int32(layer))
        ref = ops.decode_attn_paged(q, stack(pool_k), stack(pool_v), table,
                                    lens, layer=jnp.int32(layer),
                                    use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(one))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    assert np.all(np.asarray(got[0]) == 0.0)         # length-0 row


# ---------------------------------------------------------------------------
# paged loop: live pages only, pages_per_block pages to a block
# ---------------------------------------------------------------------------

LD = 128                          # head_dim the loop copies by page
LPAGE = 8
LPPB = decode_attn.pages_per_block(LPAGE, 80, 2, LD)     # 32 pages
LEDGE = [0, 1, LPAGE - 1, LPAGE, LPPB * LPAGE - 1, LPPB * LPAGE,
         LPPB * LPAGE + 1, 80 * LPAGE]                   # full table


def _loop_case(rng, lens, page, n_pages, int8, layer, layers=LAYERS):
    """q, the planes and table of a paged pool at head_dim ``LD`` (one
    pool, or ``layers`` stacked with the others' pages NaN), the kwargs
    that read it, and the reference output."""
    b = len(lens)
    q, k, v = _qkv(rng, b, n_pages * page, 2, 2, d=LD)
    pool_k, pool_v, table, _ = _paged_pool(rng, k, v, lens, page)
    if layer is not None:
        nan = jnp.full((layers,) + pool_k.shape, jnp.nan, jnp.float32)
        pool_k, pool_v = (nan.at[layer].set(x) for x in (pool_k, pool_v))
    kw = dict(layer=None if layer is None else jnp.int32(layer))
    if int8:
        qk, qv = (kv_quantize(x, LD // 2) for x in (pool_k, pool_v))
        args = (q, qk.codes, qv.codes, table, jnp.asarray(lens, jnp.int32),
                qk.scale, qk.zero, qv.scale, qv.zero)
        kw["group_size"] = LD // 2
    else:
        args = (q, pool_k, pool_v, table, jnp.asarray(lens, jnp.int32))
    ref = ops.decode_attn_paged(*args, use_pallas=False, **kw)
    return args, kw, ref


@pytest.mark.parametrize("layer", [None, 0, 1, LAYERS - 1],
                         ids=["pool", "layer0", "mid", "last"])
@pytest.mark.parametrize("int8", [False, True], ids=["dense", "int8"])
def test_paged_parity_at_block_edges(layer, int8):
    """Lengths 0, 1, a page ± 1, a block ± 1 and the whole table, against
    the gather-then-attend reference."""
    rng = np.random.default_rng(20)
    args, kw, ref = _loop_case(rng, LEDGE, LPAGE, 80, int8, layer)
    got = ops.decode_attn_paged(*args, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert np.all(np.asarray(got[0]) == 0.0)         # length-0 row


def _poisoned(rng, lens, page, n_pages, stale):
    """A pool whose table maps each row's positions from its last live
    page on to pages of NaN (stale entries and, clamped, the sentinels),
    its clean twin, and the table."""
    b = len(lens)
    q, k, v = _qkv(rng, b, n_pages * page, 2, 2, d=LD)
    live = [-(-l // page) for l in lens]
    n_live = sum(live)
    num_pages = n_live + 2 + stale                   # sentinel → last page
    clean = [np.asarray(rng.normal(size=(num_pages, page, 2, LD)),
                        np.float32) for _ in range(2)]
    table = np.full((b, n_pages), num_pages, np.int32)
    order = iter(rng.permutation(n_live))
    for row in range(b):
        for c in range(live[row]):
            p = next(order)
            table[row, c] = p
            n = min(page, lens[row] - c * page)
            clean[0][p, :n] = np.asarray(k[row, c * page:c * page + n])
            clean[1][p, :n] = np.asarray(v[row, c * page:c * page + n])
        for c in range(live[row], n_pages, 2):       # stale, every other
            table[row, c] = n_live + rng.integers(0, stale)
    poisoned = [x.copy() for x in clean]
    for x in poisoned:
        x[n_live:] = np.nan                          # every page not live
    return q, clean, poisoned, jnp.asarray(table)


@pytest.mark.parametrize("stacked", [False, True], ids=["pool", "stacked"])
def test_paged_loop_reads_no_dead_page(stacked):
    """Every page a row's table maps past its live pages, and the page a
    sentinel clamps to, is NaN: the output stays finite and equals the
    reference on the clean pool. A NaN in a live page still reaches its
    row's output, and no other row's."""
    rng = np.random.default_rng(21)
    lens = [0, 1, LPAGE + 3, LPPB * LPAGE + 5, 3 * LPAGE]
    q, clean, poisoned, table = _poisoned(rng, lens, LPAGE, 48, stale=4)
    lens_a = jnp.asarray(lens, jnp.int32)

    def run(pools, **kw):
        if stacked:          # the other layers' pages are NaN too
            pools = [jnp.stack([jnp.full_like(x, jnp.nan), x,
                                jnp.full_like(x, jnp.nan)]) for x in pools]
            kw["layer"] = jnp.int32(1)
        return np.asarray(ops.decode_attn_paged(q, *pools, table, lens_a,
                                                **kw))

    ref = run([jnp.asarray(x) for x in clean], use_pallas=False)
    got = run([jnp.asarray(x) for x in poisoned])
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)
    live_nan = [x.copy() for x in poisoned]
    live_nan[1][int(table[3, LPPB])] = np.nan        # row 3's second block
    got = run([jnp.asarray(x) for x in live_nan])
    assert not np.isfinite(got[3]).all()
    keep = [0, 1, 2, 4]
    assert np.isfinite(got[keep]).all()
    np.testing.assert_allclose(got[keep], ref[keep], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("page,n_pages,kv_heads,head_dim,ppb", [
    (16, 256, 8, 128, 16),       # granite-8b.serve.assist: 256 tokens
    (8, 256, 8, 128, 32),
    (512, 8, 8, 128, 1),         # a page longer than a block
    (16, 3, 8, 128, 3),          # a table shorter than a block
    (16, 256, 32, 256, 4),       # 64 tokens fit the VMEM budget
])
def test_pages_per_block_from_shapes(page, n_pages, kv_heads, head_dim, ppb):
    assert decode_attn.pages_per_block(page, n_pages, kv_heads,
                                       head_dim) == ppb


def test_paged_loop_table_shorter_than_a_block():
    rng = np.random.default_rng(22)
    lens = [0, 5, 3 * LPAGE, 2 * LPAGE + 1]
    assert decode_attn.pages_per_block(LPAGE, 3, 2, LD) == 3
    args, kw, ref = _loop_case(rng, lens, LPAGE, 3, False, None)
    got = ops.decode_attn_paged(*args, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# the serving-facing wrapper + failure semantics
# ---------------------------------------------------------------------------

def test_fused_decode_attn_wrapper_shapes_and_dtype():
    rng = np.random.default_rng(6)
    q, _, _, pool_k, pool_v, table = _paged_case(rng, 2, 2, 8, jnp.bfloat16)
    k, v = pool_k[None], pool_v[None]                # a stack of one layer
    positions = (jnp.asarray(LENS, jnp.int32) - 1)[:, None]
    layer = jnp.int32(0)
    out = fused_decode_attn(q[:, None], k, v, positions, table, layer)
    assert out.shape == (len(LENS), 1, 4, D) and out.dtype == jnp.bfloat16
    qk, qv = kv_quantize(k, D), kv_quantize(v, D)
    out_q = fused_decode_attn(q[:, None], qk, qv, positions, table, layer)
    assert out_q.shape == out.shape and out_q.dtype == jnp.bfloat16
    assert isinstance(qk, QuantizedKV)


def test_nan_rows_propagate_to_output():
    """Poisoned cache rows must surface as non-finite attention output —
    the engine's decode guard fails the slot on it (never silently zero)."""
    rng = np.random.default_rng(7)
    q, k, v = _qkv(rng, 3, T, 2, 2)
    k = k.at[1].set(jnp.nan)
    lens = [T, T, 0]
    pool_k, pool_v, table, _ = _paged_pool(rng, k, v, lens, page=8)
    fused = ops.decode_attn_paged(q, pool_k, pool_v, table,
                                  jnp.asarray(lens, jnp.int32))
    assert bool(jnp.all(jnp.isfinite(fused[0])))
    assert not bool(jnp.all(jnp.isfinite(fused[1])))
    assert np.all(np.asarray(fused[2]) == 0.0)       # parked row unaffected


# ---------------------------------------------------------------------------
# engine-level greedy bit-parity: use_fused_decode on vs off
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_lm():
    cfg = get_tiny_config("llama32-1b")
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _run(model, params, cfg, **ecfg_kw):
    rng = np.random.default_rng(11)
    reqs = [GenerationRequest(
                rid=i,
                prompt=rng.integers(1, cfg.vocab_size,
                                    size=int(l)).astype(np.int32),
                max_new_tokens=int(g), sampling=SamplingParams())
            for i, (l, g) in enumerate(zip([5, 11, 3, 8], [6, 4, 8, 5]))]
    eng = Engine(model, params, EngineConfig(num_slots=3, max_len=24,
                                             **ecfg_kw))
    for r in reqs:
        eng.submit(r)
    return {r.rid: r.tokens for r in eng.run()}


@pytest.mark.parametrize("storage", [
    dict(kv_dtype=jnp.float32, page_size=8),
    dict(kv_dtype=jnp.bfloat16, kv_quantized=True, page_size=8),
    # head_dim 128: the paged loop over live pages
    dict(kv_dtype=jnp.float32, page_size=4, head_dim=128),
    # bf16 pools and INT8 groups narrower than a head: the tiled grid
    dict(kv_dtype=jnp.bfloat16, page_size=8),
    dict(kv_dtype=jnp.bfloat16, kv_quantized=True, kv_group_size=8,
         page_size=8),
])
def test_engine_greedy_bit_parity_fused_vs_reference(tiny_lm, storage):
    cfg, model, params = tiny_lm
    if "head_dim" in storage:
        storage = dict(storage)
        cfg = dataclasses.replace(cfg, head_dim=storage.pop("head_dim"))
        model = build_model(cfg, remat=False)
        params = model.init(jax.random.PRNGKey(0))
    fused = _run(model, params, cfg, use_fused_decode=True, **storage)
    ref = _run(model, params, cfg, use_fused_decode=False, **storage)
    assert fused == ref
