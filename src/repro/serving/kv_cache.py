"""Slot-indexed, optionally quantized KV cache for the serving engine.

The cache is a plain pytree (it flows through ``jax.jit`` / ``lax.scan`` /
donation like the rest of the model state):

    {"k": <storage>, "v": <storage>, "pos": (num_slots,) int32}

where ``<storage>`` is either a dense ``(L, S, T, Hk, D)`` array (``L``
layers, ``S`` slots, ``T`` max_len) or a :class:`QuantizedKV` — INT8 codes
plus a per-(token, head, group) float32 scale and uint8 zero-point, groups
tiling the head_dim axis ("per-head-group"). INT8 storage costs
``1 + 5/group`` bytes per element vs 2 for bf16, i.e. ~½ the resident bytes
at ``group ≥ 32``. The planes are float32/uint8 because the TPU kernels
cannot load float16.

Reads dequantize at the attention boundary (``models/layers.attn_apply``):
the reference path is pure jnp; on TPU the Pallas ``kv_dequant`` kernel
(``repro.kernels.ops``) does the expansion in VMEM. Dispatch follows the
same ``repro.quant.matmul_impl`` switch as the weight kernels.

Writes quantize the incoming k/v: per-slot decode writes scatter one token
at each slot's own position (``pos`` is a vector — the engine convention),
prefill writes splice a whole batch of slot rows in one dispatch
(:func:`write_slot`), and the chunked prefill works on one slot's rows via
:func:`slot_rows` / :func:`set_slot_rows`.
"""
from __future__ import annotations

import dataclasses
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


def fused_decode_attn(q: jax.Array, k_entry, v_entry, positions, *,
                      table=None, layer=None,
                      block_t: int = 256) -> jax.Array:
    """Fused flash-decode read of the cache: one query token per row
    attends its history in a single Pallas program — INT8 codes dequantize
    IN-TILE, no materialized dense K/V, per-row lengths bound the K loop.

    q: (B, 1, H, D) (RoPE applied); positions: (B, 1) absolute decode
    positions (row b's cache holds lengths[b] = positions[b] + 1 live
    tokens — the current token's K/V must already be written).
    ``k_entry``/``v_entry`` are the per-layer storage: (B, T, Hk, D) slot
    rows, or — with ``table`` (B, n_pages) — the stacked page pools
    (L, P, page, Hk, D) of every layer, read at ``layer`` in place (dense
    or :class:`QuantizedKV` either way). Returns (B, 1, H, D) in
    q's dtype. The escape hatch is the caller's: ``use_fused_decode=False``
    keeps the dequant-then-attend reference path.
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
    if table is not None:
        out = ops.decode_attn_paged(q2, k_entry, v_entry, table, lengths,
                                    layer=layer, **kwargs)
    else:
        out = ops.decode_attn(q2, k_entry, v_entry, lengths,
                              block_t=block_t, **kwargs)
    return out[:, None].astype(q.dtype)


def kv_update(q: QuantizedKV, x: jax.Array, pos) -> QuantizedKV:
    """Write new tokens x (B, s, Hk, D) into the (B, T, Hk, D) storage.

    ``pos`` scalar → splice s tokens at a uniform position (the static
    serving path and the chunked prefill); ``pos`` vector (B,) → scatter
    one token per row at that row's own position (the engine decode path,
    s == 1). Scalar splices scatter per token column with drop semantics:
    columns past the cache edge (a final prefill chunk's padded tail) are
    dropped instead of shifting the write like ``dynamic_update_slice``
    would."""
    new = kv_quantize(x, q.group_size)
    if getattr(pos, "ndim", 0) == 1:
        assert x.shape[1] == 1, "per-slot writes are one token per step"
        b = x.shape[0]
        idx = jnp.arange(b)
        return QuantizedKV(
            q.codes.at[idx, pos].set(new.codes[:, 0]),
            q.scale.at[idx, pos].set(new.scale[:, 0]),
            q.zero.at[idx, pos].set(new.zero[:, 0]),
            q.group_size)
    cols = pos + jnp.arange(x.shape[1])
    return QuantizedKV(
        q.codes.at[:, cols].set(new.codes),
        q.scale.at[:, cols].set(new.scale),
        q.zero.at[:, cols].set(new.zero),
        q.group_size)


# ---------------------------------------------------------------------------
# slot-cache construction / bookkeeping
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    """Shape/storage policy for the engine's slot cache."""
    num_slots: int
    max_len: int
    dtype: object = jnp.float32      # dense storage dtype
    quantized: bool = False          # INT8 per-head-group storage
    group_size: int = 0              # 0 → head_dim (one group per head)


def init_slot_cache(model_cfg, cfg: KVCacheConfig) -> dict:
    """Fresh {"k", "v", "pos"} cache: (L, S, T, Hk, D) storage, per-slot
    positions. The layer axis leads so ``lax.scan`` over blocks slices one
    layer's (S, T, Hk, D) cache per step — identical to the static path."""
    shape = (model_cfg.num_layers, cfg.num_slots, cfg.max_len,
             model_cfg.num_kv_heads, model_cfg.resolved_head_dim)
    if cfg.quantized:
        g = cfg.group_size or model_cfg.resolved_head_dim
        assert model_cfg.resolved_head_dim % g == 0, (shape, g)
        store = QuantizedKV(
            codes=jnp.zeros(shape, jnp.uint8),
            scale=jnp.full(shape[:-1] + (shape[-1] // g,), 1e-4, jnp.float32),
            zero=jnp.zeros(shape[:-1] + (shape[-1] // g,), jnp.uint8),
            group_size=g)
        k = store
        v = QuantizedKV(jnp.zeros_like(store.codes),
                        jnp.full_like(store.scale, 1e-4),
                        jnp.zeros_like(store.zero), g)
    else:
        k = jnp.zeros(shape, cfg.dtype)
        v = jnp.zeros(shape, cfg.dtype)
    return {"k": k, "v": v, "pos": jnp.zeros((cfg.num_slots,), jnp.int32)}


def write_slot(cache: dict, slots, k_new: jax.Array, v_new: jax.Array) -> dict:
    """Splice B freshly prefilled slot rows into the big cache in one
    dispatch.

    ``slots``: (B,) int32 slot indices (a scalar is treated as B == 1);
    ``k_new``/``v_new``: (L, B, W, Hk, D) dense floats (the batched prefill
    mini-caches), written at [:, slots[b], :W]. The scatter drops rows
    whose slot index is out of range — the batch-bucket padding convention
    (padding rows carry slot == num_slots) — and any token column past the
    cache edge, so padded bucket tails never hold live tokens."""
    out = dict(cache)
    slots = jnp.atleast_1d(jnp.asarray(slots, jnp.int32))
    for name, new in (("k", k_new), ("v", v_new)):
        entry = cache[name]
        idx_s = slots[:, None]                      # (B, 1)
        idx_t = jnp.arange(new.shape[2])[None, :]   # (1, W)
        if isinstance(entry, QuantizedKV):
            q = kv_quantize(new, entry.group_size)
            entry = QuantizedKV(
                entry.codes.at[:, idx_s, idx_t].set(q.codes),
                entry.scale.at[:, idx_s, idx_t].set(q.scale),
                entry.zero.at[:, idx_s, idx_t].set(q.zero),
                entry.group_size)
        else:
            entry = entry.at[:, idx_s, idx_t].set(new.astype(entry.dtype))
        out[name] = entry
    return out


def slot_rows(entry, slot):
    """One slot's (L, 1, T, Hk, D) rows of the (L, S, T, Hk, D) storage
    (dense or :class:`QuantizedKV`) — the chunked prefill's working view."""
    if isinstance(entry, QuantizedKV):
        return QuantizedKV(
            jax.lax.dynamic_slice_in_dim(entry.codes, slot, 1, axis=1),
            jax.lax.dynamic_slice_in_dim(entry.scale, slot, 1, axis=1),
            jax.lax.dynamic_slice_in_dim(entry.zero, slot, 1, axis=1),
            entry.group_size)
    return jax.lax.dynamic_slice_in_dim(entry, slot, 1, axis=1)


def set_slot_rows(entry, slot, rows):
    """Write a (L, 1, T, Hk, D) slot row (from :func:`slot_rows`) back into
    the (L, S, T, Hk, D) storage."""
    if isinstance(entry, QuantizedKV):
        return QuantizedKV(
            jax.lax.dynamic_update_slice_in_dim(
                entry.codes, rows.codes, slot, axis=1),
            jax.lax.dynamic_update_slice_in_dim(
                entry.scale, rows.scale, slot, axis=1),
            jax.lax.dynamic_update_slice_in_dim(
                entry.zero, rows.zero, slot, axis=1),
            entry.group_size)
    return jax.lax.dynamic_update_slice_in_dim(
        entry, rows.astype(entry.dtype), slot, axis=1)


# ---------------------------------------------------------------------------
# paged storage (the block-table layout — serving/paging.py owns the
# allocator/refcounts; this layer owns the device arrays and the scatters)
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
    pools through per-row page maps — the paged mirror of :func:`write_slot`.

    ``page_map``: (B, ceil(W/page_size)) int32 physical page per page-column
    of each row. Entries equal to the sentinel (== num_pages) drop their
    whole page column — batch-bucket padding rows carry all-sentinel maps,
    and a row's map holds exactly ceil(prompt_len/page_size) live pages, so
    the bucket-padded tail past the last allocated page is dropped (the
    garbage inside the last live page is overwritten by decode writes in
    position order before it is ever attended, as on the slot path)."""
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
    to a slot-cache row."""
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
    """Resident bytes of the K/V storage (excludes the tiny pos vector)."""
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
        leaves = ([entry.codes, entry.scales]
                  if isinstance(entry, QuantizedKV) else [entry])
        for leaf in leaves:
            if jnp.issubdtype(leaf.dtype, jnp.floating) and not bool(
                    jnp.all(jnp.isfinite(leaf))):
                return False
    return True


__all__ = ["QuantizedKV", "KVCacheConfig", "init_slot_cache", "write_slot",
           "slot_rows", "set_slot_rows", "cache_bytes", "cache_is_finite",
           "kv_quantize", "kv_dequantize", "kv_update", "fused_decode_attn",
           "init_paged_storage", "write_pages", "paged_view", "take_pages",
           "put_pages"]
