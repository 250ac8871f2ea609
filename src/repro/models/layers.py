"""Shared model building blocks, pure JAX.

Conventions:
- Linear weights are stored ``(d_in, d_out)`` (activation @ weight). The
  compression library uses the paper orientation ``(d_out, d_in)``; the
  driver transposes at the boundary.
- ``capture`` dicts collect pre-matmul activations for calibration; they are
  only populated on the (non-scanned) per-block capture path.
- All blocks take a ShardingRules (``rules``) and place logical-axis
  constraints; with rules=NO_RULES they are no-ops.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.obs.spans import scope
from repro.quant import QTensor
from repro.serving.kv_cache import (QuantizedKV, fused_decode_attn,
                                    kv_dequantize, kv_update, kv_quantize,
                                    paged_view)
from repro.sharding import ShardingRules, NO_RULES, hint


# ---------------------------------------------------------------------------
# linear dispatch: dense array or packed QTensor, one entry point
# ---------------------------------------------------------------------------

def linear_apply(w, x: jax.Array) -> jax.Array:
    """y = x @ w — the single dispatch every model linear routes through.

    ``w`` is either a dense ``(d_in, d_out)`` array (stored orientation) or
    a packed :class:`~repro.quant.QTensor` in paper orientation
    ``(d_out, d_in)``, whose matmul contracts against dequantized rows —
    the same product, read at ~4 bits/weight. QTensor execution follows
    ``repro.quant.matmul_impl``: fused Pallas dequant-matmul on TPU,
    reference dequant elsewhere, ``"kernel"`` (interpret mode) for tests.
    """
    if isinstance(w, QTensor):
        lead = x.shape[:-1]
        y = w.matmul_dispatch(x.reshape(-1, x.shape[-1]))
        return y.reshape(*lead, y.shape[-1]).astype(x.dtype)
    return x @ w


def expert_apply(w, x: jax.Array, *, per_expert: bool = False) -> jax.Array:
    """Batched per-expert linear: x (T, d) → (T, E, f), or per-expert
    inputs x (E, T, d) → (E, T, f) with ``per_expert=True``.

    ``w`` is a stacked dense ``(E, d, f)`` expert weight or a QTensor leaf
    whose children carry a leading expert dim (aux shape stays the
    per-expert ``(f, d)``); the QTensor path vmaps the dequant-matmul over
    the expert axis — experts stay packed in HBM on the decode path. This
    is the single per-expert dispatch site (the MoE down-proj feeds its
    per-expert activations through ``per_expert=True``)."""
    if isinstance(w, QTensor):
        y = jax.vmap(lambda qt, xe: qt.matmul_dispatch(xe),
                     in_axes=(0, 0 if per_expert else None))(w, x)
        return (y if per_expert else y.transpose(1, 0, 2)).astype(x.dtype)
    if per_expert:
        return jnp.einsum("etd,edf->etf", x, w)
    return jnp.einsum("td,edf->tef", x, w)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms / rope / activations
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (B, S, H, D) with even D; positions: (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs       # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mlp_act(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu2":                      # nemotron squared-ReLU
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# chunked causal flash attention (online softmax; never materializes S×S)
# ---------------------------------------------------------------------------

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, q_offset=0,
                    q_chunk: int = 1024, kv_chunk: int = 1024,
                    p_dtype=jnp.float32, kv_pages=None) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Skv, Hk, D) with H % Hk == 0.

    Double-chunked online-softmax attention in pure JAX: an outer scan over
    query chunks and an inner scan over KV chunks carrying (m, l, acc). Peak
    memory is O(q_chunk × kv_chunk) per head — required for the 32k-prefill
    and 4k-train shapes at production width (DESIGN.md §4).
    ``q_offset`` is the absolute position of q[0] (decode/prefill continua).

    ``kv_pages=(block_table, layer)`` switches K/V to the paged layout:
    k, v are the stacked page POOLS of every layer — (L, P, page, Hk, D)
    dense or a :class:`~repro.serving.kv_cache.QuantizedKV` with those
    leading dims — read at ``layer``, and ``block_table`` (B, n_pages)
    int32 maps each row's kv positions to physical pages. Each inner step
    gathers only its own kv_chunk worth of that layer's pages in-tile
    (quantized pools dequantize the gathered tile), so neither the layer's
    pool nor the contiguous (B, Skv) view is materialized. Sentinel table
    entries (== P) clip to the last physical page; their garbage is strictly
    beyond every live query's causal mask, so outputs are bit-identical to
    the contiguous path over the same written tokens.
    """
    b, sq, h, d = q.shape
    if kv_pages is not None:
        table, layer = kv_pages
        store = k.codes if isinstance(k, QuantizedKV) else k
        page_size = store.shape[2]
        hk = store.shape[-2]
        skv = table.shape[1] * page_size
    else:
        _, skv, hk, _ = k.shape
    assert h % hk == 0
    g = h // hk
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    # pad seq dims to multiples of the chunk
    def pad_to(x, mult, axis):
        n = x.shape[axis]
        pad = (-n) % mult
        if pad == 0:
            return x, n
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths), n
    q, sq0 = pad_to(q, q_chunk, 1)
    scale = 1.0 / math.sqrt(d)

    if kv_pages is not None:
        # page-aligned kv chunks: gather pages_per_chunk pages per step
        ppc = max(kv_chunk // page_size, 1)
        kv_chunk = ppc * page_size
        npg = table.shape[1]
        npg_p = -(-npg // ppc) * ppc
        if npg_p != npg:                     # sentinel-pad the table itself
            table = jnp.pad(table, ((0, 0), (0, npg_p - npg)),
                            constant_values=store.shape[1])
        skv0, skv_p = skv, npg_p * page_size

        def fetch(ki):
            pages = jax.lax.dynamic_slice(table, (0, ki * ppc), (b, ppc))
            def grab(pool):
                gt = pool[layer, pages]      # (B, ppc, page, ...)
                return gt.reshape(b, kv_chunk, *pool.shape[3:])
            if isinstance(k, QuantizedKV):
                return (kv_dequantize(QuantizedKV(
                            grab(k.codes), grab(k.scale), grab(k.zero),
                            k.group_size), q.dtype),
                        kv_dequantize(QuantizedKV(
                            grab(v.codes), grab(v.scale), grab(v.zero),
                            v.group_size), q.dtype))
            return grab(k), grab(v)
    else:
        k, skv0 = pad_to(k, kv_chunk, 1)
        v, _ = pad_to(v, kv_chunk, 1)
        skv_p = k.shape[1]
        kg = k.reshape(b, skv_p // kv_chunk, kv_chunk, hk, d)
        vg = v.reshape(b, skv_p // kv_chunk, kv_chunk, hk, d)

        def fetch(ki):
            return kg[:, ki], vg[:, ki]

    sq_p = q.shape[1]
    nq, nk = sq_p // q_chunk, skv_p // kv_chunk
    qg = q.reshape(b, nq, q_chunk, h, d)

    q_pos = (jnp.arange(sq_p) + q_offset).reshape(nq, q_chunk)
    k_pos = jnp.arange(skv_p).reshape(nk, kv_chunk)
    kv_valid = (jnp.arange(skv_p) < skv0).reshape(nk, kv_chunk)

    def q_step(_, qi):
        qc = qg[:, qi]                              # (B, qc, H, D)
        qpos = q_pos[qi]

        @jax.checkpoint   # recompute P in backward: true flash-attention
        def kv_step(carry, ki):                     # memory (no saved scores)
            m, l, acc = carry
            kc, vc = fetch(ki)                      # (B, kc, Hk, D)
            s = _scores(qc, kc, g) * scale          # (B, H, qc, kc)
            mask = kv_valid[ki][None, None, None, :]
            if causal:
                mask = mask & (k_pos[ki][None, None, None, :] <=
                               qpos[None, None, :, None])
            s = jnp.where(mask, s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard rows with no valid keys yet
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask, p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + p.sum(axis=-1)
            pv = _pv(p.astype(p_dtype), vc, g)      # (B, H, qc, D)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.transpose(0, 2, 1, 3)      # (B, qc, H, D)

    _, chunks = jax.lax.scan(q_step, None, jnp.arange(nq))
    out = chunks.transpose(1, 0, 2, 3, 4).reshape(b, sq_p, h, d)
    return out[:, :sq0].astype(q.dtype)


def _scores(qc: jax.Array, kc: jax.Array, g: int) -> jax.Array:
    """(B,qc,H,D) x (B,kc,Hk,D) -> (B,H,qc,kc) with GQA group expansion."""
    b, qn, h, d = qc.shape
    kn, hk = kc.shape[1], kc.shape[2]
    qh = qc.reshape(b, qn, hk, g, d).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bnkd->bkgqn", qh, kc.astype(jnp.float32))
    return s.reshape(b, h, qn, kn)


def _pv(p: jax.Array, vc: jax.Array, g: int) -> jax.Array:
    """(B,H,qc,kc) x (B,kc,Hk,D) -> (B,H,qc,D), f32 accumulation.

    p may be bf16 (the TPU-flash convention: f32 softmax statistics, bf16
    probabilities into the MXU) — §Perf 'bf16 P·V' iteration."""
    b, h, qn, kn = p.shape
    hk = h // g
    pg = p.reshape(b, hk, g, qn, kn)
    out = jnp.einsum("bkgqn,bnkd->bkgqd", pg, vc.astype(p.dtype),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, qn, -1)


def paged_write(entry, layer, table, pos, new):
    """Scatter ``new`` (B, s, Hk, D) tokens into layer ``layer`` of the
    stacked page pools (L, P, page, Hk, D) through the block table, in
    place: each token lands at ``[layer, page, offset]`` and nothing else
    of the stack is read or rewritten.

    ``pos`` vector (B,) with s == 1 (the engine decode path: each row
    writes at its own position) or scalar with s >= 1 (the chunked
    prefill: s consecutive positions from ``pos``). A position landing on
    a sentinel table entry — a parked slot's row, or a final chunk's
    padded tail past the request's allocated pages — is dropped, never
    written (in particular nothing ever lands in another request's page)."""
    b, s = new.shape[0], new.shape[1]
    _, num_pages, page_size = (entry.codes if isinstance(entry, QuantizedKV)
                               else entry).shape[:3]
    npg = table.shape[1]
    if getattr(pos, "ndim", 0) == 1:
        assert s == 1, "per-slot paged writes are one token per step"
        cols = pos[:, None]                               # (B, 1)
    else:
        cols = jnp.broadcast_to((pos + jnp.arange(s))[None, :], (b, s))
    valid = cols < npg * page_size
    pidx = jnp.clip(cols // page_size, 0, npg - 1)
    pages = jnp.take_along_axis(table, pidx, axis=1)      # (B, s)
    pages = jnp.where(valid, pages, num_pages)            # OOB → dropped
    offs = cols % page_size
    if isinstance(entry, QuantizedKV):
        qn = kv_quantize(new, entry.group_size)
        return QuantizedKV(entry.codes.at[layer, pages, offs].set(qn.codes),
                           entry.scale.at[layer, pages, offs].set(qn.scale),
                           entry.zero.at[layer, pages, offs].set(qn.zero),
                           entry.group_size)
    return entry.at[layer, pages, offs].set(new.astype(entry.dtype))


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     q_positions: jax.Array, rules: ShardingRules = NO_RULES,
                     p_dtype=jnp.float32) -> jax.Array:
    """Attention of new tokens against a (possibly seq-sharded) KV cache.

    q: (B, S, H, D); caches: (B, Smax, Hk, D); q_positions: (B, S) absolute
    positions (causal mask: key index ≤ query position — the new tokens'
    K/V must already be written into the cache).
    Plain einsum + masked softmax: with the cache seq axis sharded over
    'model', XLA lowers max/sum to the flash-decoding all-reduce pattern.
    """
    b, sq, h, d = q.shape
    hk = k_cache.shape[2]
    g = h // hk
    s = _scores(q, k_cache, g)                       # (B, H, Sq, Smax)
    s = s / math.sqrt(d)
    k_idx = jnp.arange(k_cache.shape[1])
    valid = k_idx[None, None, :] <= q_positions[:, :, None]   # (B, Sq, Smax)
    s = jnp.where(valid[:, None, :, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = _pv(p.astype(p_dtype), v_cache, g)         # (B, H, Sq, D)
    return out.transpose(0, 2, 1, 3).astype(q.dtype) # (B, Sq, H, D)


# ---------------------------------------------------------------------------
# attention + MLP blocks (dense family)
# ---------------------------------------------------------------------------

def attn_params(key, cfg, dtype=jnp.float32):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, cfg.num_heads * hd, dtype),
        "wk": dense_init(ks[1], d, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], d, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.num_heads * hd, d, dtype),
        "norm": jnp.ones((d,), dtype),
    }


def mlp_params(key, cfg, dtype=jnp.float32, d_ff: Optional[int] = None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"wu": dense_init(ks[0], d, f, dtype),
         "wd": dense_init(ks[1], f, d, dtype),
         "norm": jnp.ones((d,), dtype)}
    if cfg.mlp_act == "silu":                        # gated
        p["wg"] = dense_init(ks[2], d, f, dtype)
    return p


def attn_apply(p, x, cfg, rules: ShardingRules = NO_RULES, *,
               positions=None, capture=None,
               kv_cache=None, cache_pos=None, attend_cache: bool = False,
               block_table=None, layer=None, fused_decode: bool = False,
               attn_chunk: int = 1024, attn_p_dtype=jnp.float32):
    """Pre-norm attention block (residual added by caller).

    Returns (out, new_kv): new_kv is (k, v) of this call when kv_cache is
    None (training / prefill cache fill) or the updated (k_cache, v_cache)
    for decode. ``cache_pos`` is the write position: a scalar (uniform
    across the batch — the static serving path) or a per-row (B,) vector
    (the continuous-batching engine, where each slot decodes at its own
    position; requires s == 1). Cache entries may be dense arrays or
    INT8 :class:`~repro.serving.kv_cache.QuantizedKV` storage — quantized
    caches quantize on write and dequantize on the attention read.

    ``attend_cache=True`` is the chunked-prefill contract: for s > 1 with a
    scalar ``cache_pos``, this chunk's K/V is written at
    [cache_pos, cache_pos + s) first (token columns past the cache edge —
    a final chunk's padded tail — are dropped, never shifted), then the
    queries attend the CACHE rows under the offset causal mask
    (key index <= cache_pos + query offset) instead of only the fresh
    chunk, so earlier chunks of the same prompt are visible. Quantized
    caches attend the dequantized rows, including this chunk's own
    (quantize-rounded) keys.

    ``block_table`` (B, n_pages) int32 switches the cache to the PAGED
    layout: cache entries are the stacked page pools of every layer
    (L, P, page, Hk, D), this block's being ``layer`` (int32 scalar), and
    every position routes through the table (writes via
    :func:`paged_write`, decode reads via the fused kernel or a page
    gather, chunked-prefill reads via the in-tile paged flash path), each
    at ``layer`` of the stack: no per-layer pool is sliced out or written
    back, and the returned pools are the updated stacks.
    Gathered views hold the same written values at the same positions as a
    slot-cache row (everything else is causally masked), so paged greedy
    output is bit-identical to the slot path, dense and INT8 alike.

    ``fused_decode=True`` routes the s == 1 decode read (slot and paged)
    through the fused Pallas flash-decode kernel
    (:func:`~repro.serving.kv_cache.fused_decode_attn`): INT8 codes
    dequantize in-tile, per-row lengths bound the K loop, and the paged
    gather happens in the kernel's index maps — no materialized dense KV.
    False (the default) keeps the dequant-then-attend reference path.
    """
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    h, hk = cfg.num_heads, cfg.num_kv_heads
    with scope("attn_in"):
        xn = rmsnorm(x, p["norm"], cfg.norm_eps)
        if capture is not None:
            capture["attn_in"] = xn
        q = linear_apply(p["wq"], xn).reshape(b, s, h, hd)
        k = linear_apply(p["wk"], xn).reshape(b, s, hk, hd)
        v = linear_apply(p["wv"], xn).reshape(b, s, hk, hd)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        q = hint(q, rules, ("batch", None, "tp", None))
        k = hint(k, rules, ("batch", None, None, None))

    if kv_cache is None:
        with scope("attn"):
            out = flash_attention(q, k, v, causal=True, q_chunk=attn_chunk,
                                  kv_chunk=attn_chunk, p_dtype=attn_p_dtype)
        new_kv = (k, v)
    elif block_table is not None:
        assert layer is not None, "paged caches are read at a layer index"
        k_cache, v_cache = kv_cache            # stacks (L, P, page, Hk, D)
        with scope("kv_write"):
            k_cache = paged_write(k_cache, layer, block_table, cache_pos, k)
            v_cache = paged_write(v_cache, layer, block_table, cache_pos, v)
        with scope("attn"):
            if s == 1:
                if fused_decode:
                    out = fused_decode_attn(q, k_cache, v_cache, positions,
                                            table=block_table, layer=layer)
                else:
                    k_r = paged_view(k_cache, block_table, layer)
                    v_r = paged_view(v_cache, block_table, layer)
                    if isinstance(k_r, QuantizedKV):
                        k_r = kv_dequantize(k_r, q.dtype)
                        v_r = kv_dequantize(v_r, q.dtype)
                    out = decode_attention(q, k_r, v_r, positions, rules,
                                           p_dtype=attn_p_dtype)
            else:
                assert attend_cache, \
                    "paged s > 1 is the chunked-prefill contract (batched " \
                    "prefill fills a dense mini-cache, then write_pages)"
                out = flash_attention(q, k_cache, v_cache, causal=True,
                                      q_offset=cache_pos, q_chunk=attn_chunk,
                                      kv_chunk=attn_chunk,
                                      p_dtype=attn_p_dtype,
                                      kv_pages=(block_table, layer))
        new_kv = (k_cache, v_cache)
    else:
        k_cache, v_cache = kv_cache                  # (B, Smax, Hk, D)
        with scope("kv_write"):
            if isinstance(k_cache, QuantizedKV):
                k_cache = kv_update(k_cache, k, cache_pos)
                v_cache = kv_update(v_cache, v, cache_pos)
            elif getattr(cache_pos, "ndim", 0) == 1:  # per-slot positions
                assert s == 1, "per-slot cache writes are one token per step"
                rows = jnp.arange(b)
                k_cache = k_cache.at[rows, cache_pos].set(
                    k[:, 0].astype(k_cache.dtype))
                v_cache = v_cache.at[rows, cache_pos].set(
                    v[:, 0].astype(v_cache.dtype))
            elif attend_cache:
                # chunked prefill: per-column scatter so a final chunk's
                # padded tail past the cache edge is dropped, never shifted
                # back onto live rows like dynamic_update_slice would
                cols = cache_pos + jnp.arange(s)
                k_cache = k_cache.at[:, cols].set(k.astype(k_cache.dtype))
                v_cache = v_cache.at[:, cols].set(v.astype(v_cache.dtype))
            else:
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    k_cache, k.astype(k_cache.dtype), cache_pos, axis=1)
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    v_cache, v.astype(v_cache.dtype), cache_pos, axis=1)
        with scope("attn"):
            if s > 1 and attend_cache:
                # chunked prefill: attend the cache rows (which now include
                # this chunk's K/V) under the offset causal mask — flash
                # with q_offset keeps per-query numerics bit-compatible with
                # the fresh-prefill path, so chunked greedy output matches
                # the static path exactly on dense f32 caches
                if isinstance(k_cache, QuantizedKV):
                    k_r = kv_dequantize(k_cache, q.dtype)
                    v_r = kv_dequantize(v_cache, q.dtype)
                else:
                    k_r, v_r = k_cache, v_cache
                out = flash_attention(q, k_r, v_r, causal=True,
                                      q_offset=cache_pos, q_chunk=attn_chunk,
                                      kv_chunk=attn_chunk,
                                      p_dtype=attn_p_dtype)
            elif s > 1:
                # prefill: flash attention over the new tokens (assumes
                # cache_pos == 0 — the serving manager's convention);
                # decode_attention here would materialize (B,H,S,Smax)
                # scores.
                out = flash_attention(q, k, v, causal=True,
                                      q_chunk=attn_chunk, kv_chunk=attn_chunk,
                                      p_dtype=attn_p_dtype)
            elif fused_decode:
                out = fused_decode_attn(q, k_cache, v_cache, positions)
            else:
                if isinstance(k_cache, QuantizedKV):
                    k_r = kv_dequantize(k_cache, q.dtype)
                    v_r = kv_dequantize(v_cache, q.dtype)
                else:
                    k_r, v_r = k_cache, v_cache
                out = decode_attention(q, k_r, v_r, positions, rules,
                                       p_dtype=attn_p_dtype)
        new_kv = (k_cache, v_cache)

    with scope("attn_out"):
        out = hint(out, rules, ("batch", None, "tp", None))
        if capture is not None:
            capture["attn_out_in"] = out.reshape(b, s, h * hd)
        y = linear_apply(p["wo"], out.reshape(b, s, h * hd))
        return y.astype(x.dtype), new_kv


def mlp_apply(p, x, cfg, rules: ShardingRules = NO_RULES, *, capture=None):
    with scope("mlp"):
        xn = rmsnorm(x, p["norm"], cfg.norm_eps)
        if capture is not None:
            capture["mlp_in"] = xn
        if cfg.mlp_act == "silu":
            hdn = (mlp_act(linear_apply(p["wg"], xn), "silu")
                   * linear_apply(p["wu"], xn))
        else:
            hdn = mlp_act(linear_apply(p["wu"], xn), cfg.mlp_act)
        hdn = hint(hdn, rules, ("batch", None, "tp"))
        if capture is not None:
            capture["mlp_down_in"] = hdn
        return linear_apply(p["wd"], hdn).astype(x.dtype)


__all__ = ["dense_init", "embed_init", "rmsnorm", "rope", "mlp_act",
           "flash_attention", "decode_attention", "paged_write",
           "attn_params", "mlp_params",
           "attn_apply", "mlp_apply", "linear_apply", "expert_apply"]
