"""Paged, optionally quantized KV cache for the serving engine.

The cache is a plain pytree (it flows through ``jax.jit`` / ``lax.scan`` /
donation like the rest of the model state):

    {"k": <storage>, "v": <storage>}

where ``<storage>`` is a page pool of every layer: either a dense
``(L, P, page, Hk, D)`` array (``L`` layers, ``P`` pages of ``page``
tokens) or a :class:`QuantizedKV` — INT8 codes plus a per-(token, head,
group) float32 scale and uint8 zero-point, groups tiling the head_dim axis
("per-head-group"). INT8 storage costs ``1 + 5/group`` bytes per element
vs 2 for bf16, i.e. ~½ the resident bytes at ``group ≥ 32``. The planes
are float32/uint8 because the TPU kernels cannot load float16.
``serving/paging.py`` owns the allocator and refcounts; this module owns
the device arrays and the scatters.

Each request's positions route to physical pages through a block table
row. Writes quantize the incoming k/v: prefill scatters a whole batch of
dense mini-caches through per-row page maps in one dispatch
(:func:`write_pages`), decode and the chunked prefill write at each
layer's index of the stacked pools (``models/layers.paged_write``). Reads
dequantize at the attention boundary (``models/layers.attn_apply``): the
fused decode kernel in-tile (:func:`fused_decode_attn`), the reference
paths through :func:`kv_dequantize`, which follows the same
``repro.quant.matmul_impl`` switch as the weight kernels.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.quant.qtensor import resolved_impl


class QuantizedKV(NamedTuple):
    """INT8 cache storage: codes + per-(…, head, group) affine params.

    ``codes``: (..., T, Hk, D) uint8; ``scale``: (..., T, Hk, D/g) float32;
    ``zero``: (..., T, Hk, D/g) uint8 (the zero-point is an integer code).
    ``group_size`` is static (pytree aux), so QuantizedKV leaves
    scan/stack/donate like dense arrays with the group layout baked in.
    """
    codes: jax.Array
    scale: jax.Array
    zero: jax.Array
    group_size: int

    def nbytes(self) -> int:
        return int(self.codes.size * self.codes.dtype.itemsize
                   + self.scale.size * self.scale.dtype.itemsize
                   + self.zero.size * self.zero.dtype.itemsize)


jax.tree_util.register_pytree_node(
    QuantizedKV,
    lambda q: ((q.codes, q.scale, q.zero), q.group_size),
    lambda aux, ch: QuantizedKV(ch[0], ch[1], ch[2], aux))


# ---------------------------------------------------------------------------
# quantize / dequantize (per-head-group asymmetric INT8)
# ---------------------------------------------------------------------------

def kv_quantize(x: jax.Array, group_size: int) -> QuantizedKV:
    """x: (..., D) float → codes (..., D) uint8 + scale (..., D/g) f32 and
    zero (..., D/g) uint8.

    Asymmetric min/max over each head_dim group, with the grid stretched to
    include 0 (the ONNX convention) so the zero-point is always exactly
    representable — one-sided groups (e.g. a constant bias channel) round-
    trip instead of collapsing, and zero-initialized cache rows stay
    exactly zero. Scales are clamped to a small positive minimum."""
    d = x.shape[-1]
    assert d % group_size == 0, (d, group_size)
    g = x.reshape(*x.shape[:-1], d // group_size, group_size).astype(jnp.float32)
    gmax = jnp.maximum(g.max(axis=-1), 0.0)
    gmin = jnp.minimum(g.min(axis=-1), 0.0)
    scale = jnp.maximum((gmax - gmin) / 255.0, 1e-4)
    zero = jnp.clip(jnp.round(-gmin / scale), 0.0, 255.0)
    codes = jnp.clip(jnp.round(g / scale[..., None]) + zero[..., None],
                     0.0, 255.0).astype(jnp.uint8)
    return QuantizedKV(codes=codes.reshape(*x.shape[:-1], d),
                       scale=scale,
                       zero=zero.astype(jnp.uint8),
                       group_size=group_size)


def _reference_dequant(q: QuantizedKV, dtype) -> jax.Array:
    d = q.codes.shape[-1]
    g = q.codes.reshape(*q.codes.shape[:-1], d // q.group_size,
                        q.group_size).astype(jnp.float32)
    deq = (g - q.zero[..., None].astype(jnp.float32)) \
        * q.scale[..., None].astype(jnp.float32)
    return deq.reshape(q.codes.shape).astype(dtype)


def kv_dequantize(q: QuantizedKV, dtype=jnp.float32) -> jax.Array:
    """Dense (..., T, Hk, D) values. Follows ``repro.quant.matmul_impl``:
    the Pallas kernel on the "kernel" path (native on TPU, interpret in
    tests), pure jnp on "reference" (the CPU default)."""
    if resolved_impl() == "reference":
        return _reference_dequant(q, dtype)
    from repro.kernels import ops     # local: kernels are TPU-optional
    lead = q.codes.shape[:-2]
    hk, d = q.codes.shape[-2:]
    rows = 1
    for s in lead:
        rows *= s
    flat = ops.kv_dequant(q.codes.reshape(rows, hk * d),
                          q.scale.reshape(rows, -1),
                          q.zero.reshape(rows, -1), q.group_size)
    return flat.reshape(*lead, hk, d).astype(dtype)


def fused_decode_attn(q: jax.Array, k_entry, v_entry, positions, table,
                      layer) -> jax.Array:
    """Fused flash-decode read of the cache: one query token per row
    attends its history in a single Pallas program — INT8 codes dequantize
    IN-TILE, no materialized dense K/V, per-row lengths bound the K loop.

    q: (B, 1, H, D) (RoPE applied); positions: (B, 1) absolute decode
    positions (row b's cache holds lengths[b] = positions[b] + 1 live
    tokens — the current token's K/V must already be written).
    ``k_entry``/``v_entry`` are the stacked page pools (L, P, page, Hk, D)
    of every layer (dense or :class:`QuantizedKV`), read at ``layer`` in
    place through ``table`` (B, n_pages). Returns (B, 1, H, D) in q's
    dtype. The escape hatch is the caller's: ``use_fused_decode=False``
    keeps the gather-then-attend reference path.
    """
    from repro.kernels import ops     # local: kernels are TPU-optional
    lengths = positions[:, 0].astype(jnp.int32) + 1
    q2 = q[:, 0]
    quant = isinstance(k_entry, QuantizedKV)
    kwargs = {}
    if quant:
        kwargs = dict(k_scale=k_entry.scale, k_zero=k_entry.zero,
                      v_scale=v_entry.scale, v_zero=v_entry.zero,
                      group_size=k_entry.group_size)
        k_entry, v_entry = k_entry.codes, v_entry.codes
    out = ops.decode_attn_paged(q2, k_entry, v_entry, table, lengths,
                                layer=layer, **kwargs)
    return out[:, None].astype(q.dtype)


# ---------------------------------------------------------------------------
# page pools and their scatters
# ---------------------------------------------------------------------------

def init_paged_storage(model_cfg, num_pages: int, page_size: int,
                       dtype=jnp.float32, quantized: bool = False,
                       group_size: int = 0) -> dict:
    """Fresh {"k", "v"} page pools: (L, P, page_size, Hk, D) storage.

    Every page is allocatable (there is no pinned page); ``num_pages`` (the
    value P itself) is the SENTINEL page index — block-table entries equal
    to it drop writes (JAX scatter OOB semantics) and clip reads to the last
    physical page, whose garbage is always causally masked or discarded.
    Quantized pools carry per-(page, token, head, group) scales, so pages
    move (spill/restore, prefix sharing) without any re-quantization."""
    shape = (model_cfg.num_layers, num_pages, page_size,
             model_cfg.num_kv_heads, model_cfg.resolved_head_dim)
    if quantized:
        g = group_size or model_cfg.resolved_head_dim
        assert model_cfg.resolved_head_dim % g == 0, (shape, g)
        k = QuantizedKV(
            codes=jnp.zeros(shape, jnp.uint8),
            scale=jnp.full(shape[:-1] + (shape[-1] // g,), 1e-4, jnp.float32),
            zero=jnp.zeros(shape[:-1] + (shape[-1] // g,), jnp.uint8),
            group_size=g)
        v = QuantizedKV(jnp.zeros_like(k.codes), jnp.full_like(k.scale, 1e-4),
                        jnp.zeros_like(k.zero), g)
    else:
        k = jnp.zeros(shape, dtype)
        v = jnp.zeros(shape, dtype)
    return {"k": k, "v": v}


def write_pages(kv: dict, page_map, k_new: jax.Array, v_new: jax.Array,
                page_size: int) -> dict:
    """Splice B freshly prefilled (L, B, W, Hk, D) mini-caches into the page
    pools through per-row page maps, in one dispatch.

    ``page_map``: (B, ceil(W/page_size)) int32 physical page per page-column
    of each row. Entries equal to the sentinel (== num_pages) drop their
    whole page column — batch-bucket padding rows carry all-sentinel maps,
    and a row's map holds exactly ceil(prompt_len/page_size) live pages, so
    the bucket-padded tail past the last allocated page is dropped (the
    garbage inside the last live page is overwritten by decode writes in
    position order before it is ever attended)."""
    out = dict(kv)
    w = k_new.shape[2]
    pidx = jnp.arange(w) // page_size                   # (W,) static
    offs = jnp.arange(w) % page_size
    for name, new in (("k", k_new), ("v", v_new)):
        entry = kv[name]
        pages = page_map[:, pidx]                       # (B, W)
        off = jnp.broadcast_to(offs[None, :], pages.shape)
        if isinstance(entry, QuantizedKV):
            q = kv_quantize(new, entry.group_size)
            entry = QuantizedKV(
                entry.codes.at[:, pages, off].set(q.codes),
                entry.scale.at[:, pages, off].set(q.scale),
                entry.zero.at[:, pages, off].set(q.zero),
                entry.group_size)
        else:
            entry = entry.at[:, pages, off].set(new.astype(entry.dtype))
        out[name] = entry
    return out


def paged_view(entry, table, layer):
    """Gather each block-table row's pages of one layer into a contiguous
    per-request view: stacked pools (L, P, page, Hk, D) + table
    (B, n_pages) + layer index → (B, n_pages·page, Hk, D), read straight
    from the stack (no per-layer pool is sliced out). Sentinel table
    entries clip to the last physical page — those positions are strictly
    beyond every live query's causal mask, so the view attends identically
    to a contiguous cache holding the same tokens."""
    def gather(pool):
        b, npg = table.shape
        g = pool[layer, table]                          # (B, npg, page, ...)
        return g.reshape(b, npg * pool.shape[2], *pool.shape[3:])
    if isinstance(entry, QuantizedKV):
        return QuantizedKV(gather(entry.codes), gather(entry.scale),
                           gather(entry.zero), entry.group_size)
    return gather(entry)


def take_pages(entry, pages):
    """Gather whole pages across all layers: (L, P, page, …) + (N,) int32 →
    (L, N, page, …). The spill path (preemption) reads through this."""
    return jax.tree.map(lambda a: jnp.take(a, pages, axis=1), entry)


def put_pages(entry, pages, rows):
    """Scatter whole-page payloads (from :func:`take_pages`) back into the
    pool at ``pages``; sentinel indices drop (the pow2 padding convention
    of the spill/restore helpers)."""
    return jax.tree.map(lambda a, r: a.at[:, pages].set(r.astype(a.dtype)),
                        entry, rows)


def cache_bytes(cache: dict) -> int:
    """Resident bytes of the K/V storage."""
    total = 0
    for name in ("k", "v"):
        entry = cache[name]
        if isinstance(entry, QuantizedKV):
            total += entry.nbytes()
        else:
            total += int(entry.size * entry.dtype.itemsize)
    return total


def cache_is_finite(cache: dict) -> bool:
    """Debug aid for the engine's non-finite-logit guard: True when every
    float plane of the K/V storage is finite. When the decode guard fails
    a slot, this localizes whether the corruption already lives in the
    cache (bad prefill write, spilled-page bit rot) or only in that step's
    activations. INT8 code planes are skipped (integers are always
    finite); quantized per-group scales are checked. One device reduction
    per plane — a diagnostic, not a per-step check."""
    for name in ("k", "v"):
        entry = cache[name]
        leaves = ([entry.codes, entry.scale]
                  if isinstance(entry, QuantizedKV) else [entry])
        for leaf in leaves:
            if jnp.issubdtype(leaf.dtype, jnp.floating) and not bool(
                    jnp.all(jnp.isfinite(leaf))):
                return False
    return True


__all__ = ["QuantizedKV", "cache_bytes", "cache_is_finite", "kv_quantize",
           "kv_dequantize", "fused_decode_attn", "init_paged_storage",
           "write_pages", "paged_view", "take_pages", "put_pages"]
