"""Dense-family decoder LM (covers [dense], [audio], [vlm] archs).

- lax.scan over stacked per-layer params (compile time ~ one layer).
- jax.checkpoint (remat) around each block for training.
- Chunked softmax-xent so full (B, S, V) logits are never materialized.
- [audio]: input is precomputed frame embeddings (EnCodec frontend stub).
- [vlm]: precomputed patch embeddings are prepended to token embeddings
  (InternViT frontend stub); loss is masked to text positions.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.obs.spans import scope
from repro.quant import QTensor
from repro.sharding import ShardingRules, NO_RULES, hint  # noqa: F401 (re-export)

Params = Dict[str, Any]


def block_params(key, cfg: ModelConfig, dtype=jnp.float32):
    ka, km = jax.random.split(key)
    return {"attn": L.attn_params(ka, cfg, dtype),
            "mlp": L.mlp_params(km, cfg, dtype)}


def block_apply(p, x, cfg, rules=NO_RULES, *, positions=None, capture=None,
                kv_cache=None, cache_pos=None, attend_cache: bool = False,
                block_table=None, layer=None, fused_decode: bool = False,
                attn_chunk: int = 1024, attn_p_dtype=jnp.float32):
    a, new_kv = L.attn_apply(p["attn"], x, cfg, rules, positions=positions,
                             capture=capture, kv_cache=kv_cache,
                             cache_pos=cache_pos, attend_cache=attend_cache,
                             block_table=block_table, layer=layer,
                             fused_decode=fused_decode,
                             attn_chunk=attn_chunk,
                             attn_p_dtype=attn_p_dtype)
    with scope("attn_out"):
        x = x + a
    m = L.mlp_apply(p["mlp"], x, cfg, rules, capture=capture)
    with scope("mlp"):
        x = x + m
    return x, new_kv


@dataclasses.dataclass
class DenseModel:
    cfg: ModelConfig
    rules: ShardingRules = NO_RULES
    param_dtype: Any = jnp.float32
    remat: bool = True
    logit_chunk: int = 512
    attn_chunk: int = 1024
    # bf16 P·V (f32 softmax stats, bf16 probs into the MXU): the TPU-flash
    # convention; default f32 so tests compare bit-tight. §Perf iteration.
    attn_p_dtype: Any = jnp.float32
    # unroll=True replaces every lax.scan whose body repeats (layers,
    # microbatches) with a python loop and makes inner chunk scans
    # single-iteration: used by the dry-run COST lowering, where XLA's
    # cost_analysis counts loop bodies once (see analysis/roofline.py).
    unroll: bool = False
    # route s == 1 decode cache reads through the fused Pallas flash-decode
    # kernel (in-tile INT8 dequant, length-bounded K loop) instead of the
    # dequant-then-attend reference. Off by default so the static paths
    # keep their exact numerics; the serving engine flips it per
    # EngineConfig.use_fused_decode.
    use_fused_decode: bool = False

    # -- params ------------------------------------------------------------
    def init(self, key) -> Params:
        cfg = self.cfg
        k_emb, k_blk, k_head = jax.random.split(key, 3)
        blocks = jax.vmap(lambda k: block_params(k, cfg, self.param_dtype))(
            jax.random.split(k_blk, cfg.num_layers))
        params = {
            "embed": L.embed_init(k_emb, cfg.padded_vocab, cfg.d_model,
                                  self.param_dtype),
            "blocks": blocks,
            "final_norm": jnp.ones((cfg.d_model,), self.param_dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(k_head, cfg.d_model,
                                             cfg.padded_vocab, self.param_dtype)
        return params

    def param_logical_axes(self):
        """Logical axis names per param (stacked blocks lead with layer=None)."""
        cfg = self.cfg
        ax = {
            "embed": (None, "tp"),          # vocab replicated, d_model TP
            "final_norm": (None,),
            "blocks": {
                "attn": {"wq": (None, "fsdp", "tp"),
                         "wk": (None, "fsdp", "tp"),
                         "wv": (None, "fsdp", "tp"),
                         "wo": (None, "tp", "fsdp"),
                         "norm": (None, None)},
                "mlp": {"wu": (None, "fsdp", "tp"),
                        "wd": (None, "tp", "fsdp"),
                        "norm": (None, None)},
            },
        }
        if cfg.mlp_act == "silu":
            ax["blocks"]["mlp"]["wg"] = (None, "fsdp", "tp")
        if not cfg.tie_embeddings:
            ax["lm_head"] = ("fsdp", "tp")  # vocab TP for chunked loss
        return ax

    # -- embedding / frontend ------------------------------------------------
    def embed(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        cfg = self.cfg
        with scope("embed"):
            if cfg.frontend == "audio_frames":
                h = batch["frames"].astype(self.param_dtype)  # stub frontend
            else:
                h = jnp.take(params["embed"], batch["tokens"], axis=0)
                if cfg.frontend == "vision_patches":
                    patches = batch["patches"].astype(h.dtype)  # stub
                    h = jnp.concatenate([patches, h], axis=1)
            return hint(h, self.rules, ("batch", None, None))

    def _block_scan(self, params, h, positions):
        cfg, rules = self.cfg, self.rules
        def body(carry, layer_p):
            x = carry
            y, _ = block_apply(layer_p, x, cfg, rules, positions=positions,
                               attn_chunk=self.attn_chunk,
                               attn_p_dtype=self.attn_p_dtype)
            # sequence-parallel carry: the scan residuals that AD must save
            # are sharded over ('batch', tp-on-seq) — Megatron-SP layout;
            # cuts per-device saved activations by the TP degree (DESIGN §4)
            return hint(y, rules, ("batch", "tp", None)), None
        with scope("blocks"):
            if self.unroll:
                for i in range(cfg.num_layers):
                    h, _ = body(h, self.block_slice(params, i))
                return h
            body_fn = jax.checkpoint(body) if self.remat else body
            h, _ = jax.lax.scan(body_fn, h, params["blocks"])
            return h

    def hidden_states(self, params, batch) -> jax.Array:
        h = self.embed(params, batch)
        b, s = h.shape[0], h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        h = self._block_scan(params, h, positions)
        return L.rmsnorm(h, params["final_norm"], self.cfg.norm_eps)

    def _head_w(self, params):
        """Dense (d_model, vocab) head weight — or a QTensor leaf when the
        head was quantized; every consumer goes through L.linear_apply."""
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]

    def _mask_pad(self, logits: jax.Array) -> jax.Array:
        """-inf the padded vocab columns (padded_vocab > vocab_size)."""
        v = self.cfg.vocab_size
        if logits.shape[-1] == v:
            return logits
        iota = jnp.arange(logits.shape[-1])
        return jnp.where(iota < v, logits, jnp.finfo(logits.dtype).min)

    def _head(self, params, h, lengths=None) -> jax.Array:
        """Logits of ``h`` (B, s, d), or of each row's last real token
        (``lengths`` (B,)): final norm, head, pad mask."""
        with scope("head"):
            if lengths is not None:
                idx = jnp.clip(lengths - 1, 0, h.shape[1] - 1)
                h = jnp.take_along_axis(h, idx[:, None, None], axis=1)
            h = L.rmsnorm(h, params["final_norm"], self.cfg.norm_eps)
            return self._mask_pad(L.linear_apply(self._head_w(params), h))

    def logits(self, params, batch) -> jax.Array:
        return self._mask_pad(L.linear_apply(self._head_w(params),
                                             self.hidden_states(params, batch)))

    # -- training loss (chunked xent, full logits never built) -------------
    def loss(self, params, batch) -> tuple:
        h = self.hidden_states(params, batch)
        labels = batch["labels"]
        if self.cfg.frontend == "vision_patches":
            # loss only on text positions (patches occupy the prefix)
            h = h[:, self.cfg.num_patches:, :] if self.cfg.num_patches else h
        nll, cnt = chunked_xent(h, self._head_w(params), labels,
                                chunk=self.logit_chunk, rules=self.rules,
                                vocab=self.cfg.vocab_size)
        return nll / jnp.maximum(cnt, 1.0), {"sum_nll": nll, "tokens": cnt}

    # -- serving ------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads,
                 cfg.resolved_head_dim)
        k = jnp.zeros(shape, dtype)
        k = hint(k, self.rules, (None, "batch", "seq_kv", None, None))
        return {"k": k, "v": k, "pos": jnp.zeros((), jnp.int32)}

    def cache_logical_axes(self):
        return {"k": (None, "batch", "seq_kv", None, None),
                "v": (None, "batch", "seq_kv", None, None),
                "pos": ()}

    def _cached_block(self, positions):
        """The block a cached pass applies: ``f(layer_p, x, **kw)`` →
        ``(x, new_kv)`` (the MoE model swaps in its own)."""
        return functools.partial(block_apply, cfg=self.cfg, rules=self.rules)

    def _cached_scan(self, params, h, cache, positions, *,
                     attend_cache: bool = False):
        """The blocks over ``h`` against the cache, one layer at a time.

        Slot caches scan each layer's (S, T, Hk, D) rows as ``xs`` and
        return them as ``ys``. Paged caches (a ``table`` (B, n_pages) in
        the cache) carry the stacked pools (L, P, page, Hk, D) through the
        loop as one buffer each and hand every block its layer index: the
        block writes and reads the pool at that index, so no layer's pool
        is sliced out or written back and the step programs' donated pools
        come back in place."""
        block = functools.partial(
            self._cached_block(positions), positions=positions,
            cache_pos=cache["pos"], attend_cache=attend_cache,
            fused_decode=self.use_fused_decode, attn_chunk=self.attn_chunk,
            attn_p_dtype=self.attn_p_dtype)
        table = cache.get("table")
        n = self.cfg.num_layers
        if table is None:
            def body(x, scanned):
                layer_p, kc, vc = scanned
                return block(layer_p, x, kv_cache=(kc, vc))
            carry = h
        else:
            def body(carry, scanned):
                x, kc, vc = carry
                layer_p, layer = scanned
                x, kv = block(layer_p, x, kv_cache=(kc, vc),
                              block_table=table, layer=layer)
                return (x, *kv), None
            carry = (h, cache["k"], cache["v"])
        with scope("blocks"):
            if self.unroll:     # python loop, static layer index (COST)
                ys = []
                for i in range(n):
                    rest = ((i,) if table is not None else
                            jax.tree.map(lambda a: a[i],
                                         (cache["k"], cache["v"])))
                    carry, y = body(carry, (self.block_slice(params, i),
                                            *rest))
                    ys.append(y)
                ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
            else:
                xs = ((cache["k"], cache["v"]) if table is None
                      else (jnp.arange(n, dtype=jnp.int32),))
                carry, ys = jax.lax.scan(body, carry,
                                         (params["blocks"], *xs))
            if table is None:
                h, (k_new, v_new) = carry, ys
            else:
                h, k_new, v_new = carry
            new_cache = {"k": k_new, "v": v_new,
                         "pos": cache["pos"] + positions.shape[1]}
        if table is not None:
            new_cache["table"] = table
        return h, new_cache

    @staticmethod
    def _base_positions(pos: jax.Array) -> jax.Array:
        """Cache position as a broadcastable base: scalar (static path) or
        per-slot vector (the engine's slot cache) → (B|1, 1)."""
        return pos[:, None] if getattr(pos, "ndim", 0) == 1 else pos

    def _prompt_positions(self, h, pos) -> jax.Array:
        """Absolute positions (B, s) of prompt tokens written from cache
        position ``pos``."""
        b, s = h.shape[0], h.shape[1]
        with scope("embed"):
            return (jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
                    + self._base_positions(pos))

    def prefill(self, params, batch, cache):
        """Teacher-forced pass that fills the cache; returns last logits."""
        h = self.embed(params, batch)
        positions = self._prompt_positions(h, cache["pos"])
        h, cache = self._cached_scan(params, h, cache, positions)
        with scope("head"):
            h = h[:, -1:, :]
        return self._head(params, h), cache

    def prefill_at(self, params, batch, cache, lengths):
        """Prefill right-padded prompts: per-row true ``lengths`` (B,).

        Same cache fill as :meth:`prefill` (cache rows past a row's length
        hold padding K/V — never attended, the causal mask stops at each
        query's position and decode overwrites them in order), but logits
        are gathered at each row's LAST REAL token (lengths-1) instead of
        the padded tail. The engine's bucketed prefill step drives this.
        """
        h = self.embed(params, batch)
        positions = self._prompt_positions(h, cache["pos"])
        h, cache = self._cached_scan(params, h, cache, positions)
        return self._head(params, h, lengths), cache

    def prefill_chunk(self, params, batch, cache, lengths):
        """One fixed-width chunk of a longer prompt against a cache that
        already holds the earlier chunks' K/V below ``cache["pos"]``.

        The chunked-prefill contract (the engine's long-prompt path): this
        chunk's K/V is written at [pos, pos + W) of the cache rows, and —
        unlike :meth:`prefill_at` — the queries ATTEND THE CACHE under the
        offset causal mask (key index <= each query's absolute position),
        so earlier chunks of the same prompt are visible and a prompt of
        any length streams through one bucket-width program. Logits are
        gathered at the chunk-local ``lengths - 1`` — meaningful only on
        the final (right-padded) chunk; callers discard earlier chunks'
        samples. A final chunk's padded tail past the cache edge is
        dropped, never written onto live rows.
        """
        h = self.embed(params, batch)
        positions = self._prompt_positions(h, cache["pos"])
        h, cache = self._cached_scan(params, h, cache, positions,
                                     attend_cache=True)
        return self._head(params, h, lengths), cache

    def decode_step(self, params, tokens, cache):
        """One decode step. tokens: (B, 1) int32. ``cache["pos"]`` is a
        scalar (uniform batch) or a per-slot (B,) vector (engine path)."""
        with scope("embed"):
            h = jnp.take(params["embed"], tokens, axis=0)
            b = h.shape[0]
            pos = cache["pos"]
            if getattr(pos, "ndim", 0) == 1:
                positions = pos[:, None]                   # (B, 1) per-slot
            else:
                positions = jnp.broadcast_to(pos[None, None], (b, 1))
        h, cache = self._cached_scan(params, h, cache, positions)
        return self._head(params, h), cache

    # -- compression protocol ------------------------------------------------
    def num_blocks(self) -> int:
        return self.cfg.num_layers

    def block_slice(self, params, i: int):
        return jax.tree.map(lambda x: x[i], params["blocks"])

    def block_apply_one(self, params, i: int, h, *, capture=False):
        cfg = self.cfg
        bp = self.block_slice(params, i)
        cap: Optional[dict] = {} if capture else None
        b, s = h.shape[0], h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        out, _ = block_apply(bp, h, cfg, self.rules, positions=positions,
                             capture=cap)
        return out, (cap or {})

    def block_linears(self, i: int):
        """(name, param_path, capture_key) — paper orientation obtained by
        transposing the stored (d_in, d_out) weight."""
        specs = [
            ("wq", ("blocks", "attn", "wq"), "attn_in"),
            ("wk", ("blocks", "attn", "wk"), "attn_in"),
            ("wv", ("blocks", "attn", "wv"), "attn_in"),
            ("wo", ("blocks", "attn", "wo"), "attn_out_in"),
            ("wu", ("blocks", "mlp", "wu"), "mlp_in"),
            ("wd", ("blocks", "mlp", "wd"), "mlp_down_in"),
        ]
        if self.cfg.mlp_act == "silu":
            specs.insert(4, ("wg", ("blocks", "mlp", "wg"), "mlp_in"))
        return specs


def chunked_xent(h: jax.Array, w_head: jax.Array, labels: jax.Array, *,
                 chunk: int = 512, rules: ShardingRules = NO_RULES,
                 vocab: int = 0):
    """Σ NLL over (B, S) without materializing (B, S, V) logits.

    Scans over sequence chunks; each chunk's logits are vocab-sharded over
    the TP axis. labels == -1 are ignored (padding); ``vocab`` masks the
    padded head columns (padded_vocab) out of the logsumexp."""
    b, s, d = h.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    n = h.shape[1] // chunk
    hc = h.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    w32 = w_head if isinstance(w_head, QTensor) else w_head.astype(jnp.float32)

    @jax.checkpoint   # recompute chunk logits in backward (never resident)
    def body(carry, xs):
        nll_acc, cnt_acc = carry
        hx, lx = xs
        logits = hint(L.linear_apply(w32, hx.astype(jnp.float32)),
                      rules, ("batch", None, "tp"))
        vocab_iota = jnp.arange(logits.shape[-1])
        if vocab and logits.shape[-1] != vocab:
            logits = jnp.where(vocab_iota[None, None, :] < vocab,
                               logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.maximum(lx, 0)
        # label-logit via iota-compare-select reduction (fuses; never
        # gathers across the vocab-sharded axis, unlike take_along_axis)
        gold = jnp.sum(jnp.where(vocab_iota[None, None, :] == lab[..., None],
                                 logits, 0.0), axis=-1)
        valid = lx >= 0
        nll = jnp.where(valid, lse - gold, 0.0)
        return (nll_acc + nll.sum(), cnt_acc + valid.sum()), None

    (nll, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.int32(0)),
                                 (hc, lc))
    return nll, cnt


__all__ = ["DenseModel", "block_params", "block_apply", "chunked_xent"]
