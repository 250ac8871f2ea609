"""Mixture-of-Experts FFN with three execution paths (DESIGN.md §4):

1. ``a2a``     — shard_map expert parallelism for the many-token shapes
                 (train / prefill): tokens flat-sharded over the whole mesh,
                 static per-(source, expert) capacity, explicit
                 ``jax.lax.all_to_all`` dispatch/return over the 'model'
                 axis, experts sharded over 'model'. Zero overcompute.
2. ``dense``   — masked all-expert compute for the few-token shapes
                 (decode): every expert weight is read once regardless of
                 routing, so the *memory* roofline term is identical to
                 ideal routing while avoiding degenerate small-token
                 all-to-alls. decode is memory-bound ⇒ the extra FLOPs sit
                 under the memory term (see EXPERIMENTS.md §Roofline note).
3. capture     — single-device path that additionally returns per-expert
                 routing masks + inputs so the compression driver can build
                 per-expert calibration covariances C_e.

Gating: full softmax over experts, top-k, renormalized (Qwen3/Grok style).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.quant import QTensor
from repro.sharding import ShardingRules, NO_RULES, hint


def moe_params(key, cfg: ModelConfig, dtype=jnp.float32):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    scale_in = 1.0 / jnp.sqrt(d)
    scale_out = 1.0 / jnp.sqrt(f)
    p = {
        "router": L.dense_init(ks[0], d, e, dtype),
        "wu": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale_in).astype(dtype),
        "wd": (jax.random.normal(ks[2], (e, f, d), jnp.float32) * scale_out).astype(dtype),
        "norm": jnp.ones((d,), dtype),
    }
    if cfg.mlp_act == "silu":
        p["wg"] = (jax.random.normal(ks[3], (e, d, f), jnp.float32) * scale_in).astype(dtype)
    return p


def moe_logical_axes(cfg: ModelConfig):
    """(L, E, d, f) expert-weight sharding: experts on TP when there are
    enough of them to tile the 16-wide production axis; otherwise (grok-1's
    8 experts) TP moves to the d_ff dim so storage still shards 256-ways
    (replicated expert weights would be 39 GB/device)."""
    e_on_tp = cfg.num_experts >= 16
    if e_on_tp:
        up, down = (None, "tp", "fsdp", None), (None, "tp", None, "fsdp")
    else:
        up, down = (None, None, "fsdp", "tp"), (None, None, "tp", "fsdp")
    ax = {"router": (None, None, None),        # (L, d, E) replicated
          "wu": up, "wd": down, "norm": (None, None)}
    if cfg.mlp_act == "silu":
        ax["wg"] = up
    return ax


def _gates(xn: jax.Array, router_w: jax.Array, k: int):
    """Top-k renormalized softmax gates. xn: (T, d) → (T, k) gates + idx."""
    logits = xn.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_i


def _expert_mm(w, xe: jax.Array) -> jax.Array:
    """(E, C, d_in) @ per-expert weight → (E, C, d_out): dense einsum or the
    stacked-QTensor dequant-matmul (packed experts on the a2a path)."""
    if isinstance(w, QTensor):
        return L.expert_apply(w, xe, per_expert=True)
    return jnp.einsum("ecd,edf->ecf", xe, w)


def _expert_ffn(xe: jax.Array, wg, wu, wd, act: str) -> jax.Array:
    """xe: (E, C, d) per-expert token buffers, expert-batched matmuls."""
    up = _expert_mm(wu, xe)
    if wg is not None:
        up = L.mlp_act(_expert_mm(wg, xe), "silu") * up
    else:
        up = L.mlp_act(up, act)
    return _expert_mm(wd, up)


def _slot_factor(cfg: ModelConfig, n_shards: int) -> int:
    """EP×TP slot replication: when the expert count doesn't cover the TP
    axis (grok-1: 8 experts on 16 shards), each expert is split into
    r = n_shards // E slots along d_ff; slot outputs are partial sums that
    the combine step adds back (DESIGN.md §4). r=1 when E % n_shards == 0."""
    e = cfg.num_experts
    if e % n_shards == 0:
        return 1
    assert n_shards % e == 0 and cfg.d_ff % (n_shards // e) == 0, \
        f"experts={e} cannot tile TP axis {n_shards}"
    return n_shards // e


def _slot_weights(p, cfg: ModelConfig, r: int, rules: ShardingRules):
    """Re-layout (E, d, f) expert weights into (E·r, d, f/r) slots (and
    (E·r, f/r, d) for the down proj). Cheap under SPMD: source and target
    are both fully sharded, so the reshard moves ~1/n of the bytes."""
    wu, wd, wg = p["wu"], p["wd"], p.get("wg")
    if r == 1:
        return wg, wu, wd
    assert not any(isinstance(w, QTensor) for w in (wu, wd, wg)), \
        "EP×TP slot re-layout (r>1) reshapes raw weights; packed experts " \
        "take the masked-dense path (moe_apply guards this)"
    e, d, f = wu.shape
    fr = f // r
    def split_up(w):
        w2 = w.reshape(e, d, r, fr).transpose(0, 2, 1, 3).reshape(e * r, d, fr)
        return hint(w2, rules, ("tp", None, None))
    wu2 = split_up(wu)
    wg2 = split_up(wg) if wg is not None else None
    wd2 = hint(wd.reshape(e, r, fr, d).reshape(e * r, fr, d),
               rules, ("tp", None, None))
    return wg2, wu2, wd2


# ---------------------------------------------------------------------------
# Path 2/3: masked all-expert compute (decode / tiny / capture)
# ---------------------------------------------------------------------------

def moe_apply_dense(p, x, cfg: ModelConfig, rules: ShardingRules = NO_RULES,
                    *, capture: Optional[dict] = None) -> jax.Array:
    b, s, d = x.shape
    xn = L.rmsnorm(x, p["norm"], cfg.norm_eps)
    t = b * s
    xf = xn.reshape(t, d)
    gates, idx = _gates(xf, p["router"], cfg.experts_per_token)
    # dense gate matrix (T, E): gate if routed else 0
    ge = jnp.zeros((t, cfg.num_experts), jnp.float32)
    ge = ge.at[jnp.arange(t)[:, None], idx].set(gates)
    ge = hint(ge, rules, ("batch", None))
    # all-expert compute, gather-weighted (decode path: memory-bound, see
    # DESIGN.md §4 — every expert weight is read once regardless of routing;
    # QTensor expert leaves cut that read to ~4 bits/weight). Dense expert
    # weights are TP-sharded on E (many experts) or f (few experts,
    # moe_logical_axes); either way the einsums partition without re-layout.
    up = L.expert_apply(p["wu"], xf)
    if cfg.mlp_act == "silu":
        up = L.mlp_act(L.expert_apply(p["wg"], xf), "silu") * up
    else:
        up = L.mlp_act(up, cfg.mlp_act)
    if capture is not None:
        capture["moe_in"] = xf
        capture["moe_mask"] = ge > 0
        capture["moe_up"] = up          # (T, E, f) pre-down activations
    wd = p["wd"]
    if isinstance(wd, QTensor):         # packed experts: stacked dequant-matmul
        ye = L.expert_apply(wd, up.transpose(1, 0, 2),
                            per_expert=True)         # (E, T, d)
        y = jnp.einsum("etd,te->td", ye, ge.astype(ye.dtype))
    else:
        y = jnp.einsum("tef,efd,te->td", up, wd, ge.astype(up.dtype))
    return y.reshape(b, s, d).astype(x.dtype)


# ---------------------------------------------------------------------------
# Path 1: shard_map all-to-all expert parallelism (train / prefill)
# ---------------------------------------------------------------------------

def moe_apply_a2a(p, x, cfg: ModelConfig, rules: ShardingRules) -> jax.Array:
    """x: (B, S, d) sharded ("batch", "tp"-on-seq, None). Tokens are
    flattened LOCALLY inside shard_map (keeping the existing layout — no
    token resharding, which would otherwise force SPMD to replicate the
    17 GB backward cotangent); dispatch is all_to_all over 'model'."""
    mesh = rules.mesh
    assert mesh is not None and rules.tp_axis is not None
    tp = rules.tp_axis
    n_exp_shards = mesh.shape[tp]
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    r = _slot_factor(cfg, n_exp_shards)   # EP×TP slots (grok: 8e → r=2)
    e_eff = e * r
    e_loc = e_eff // n_exp_shards
    dp = rules.batch_axes
    b_loc = b // rules.axis_size(dp)
    s_loc = s // n_exp_shards
    t_loc = b_loc * s_loc
    cap = max(8, int(t_loc * k / e * cfg.capacity_factor))
    cap = -(-cap // 8) * 8                               # round up to 8

    from jax.sharding import PartitionSpec as P

    xn = L.rmsnorm(x, p["norm"], cfg.norm_eps)
    xn = hint(xn, rules, ("batch", "tp", None))
    wg_w, wu_w, wd_w = _slot_weights(p, cfg, r, rules)
    has_gate = wg_w is not None

    def local(x3, router_w, wu, wd, wg_):
        # x3: (b_loc, s_loc, d); wu/wd/wg_: (e_loc, ...) local expert slots
        xt = x3.reshape(t_loc, d)                        # local flatten
        gates, idx = _gates(xt, router_w, k)             # (t_loc, k)
        flat_e = idx.reshape(-1)                         # (t_loc·k,)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1    # position within expert
        pos = pos.max(axis=-1)                           # (t_loc·k,)
        keep = pos < cap
        buf = jnp.zeros((e, cap, d), xt.dtype)
        tok_idx = jnp.repeat(jnp.arange(t_loc), k)
        buf = buf.at[flat_e, jnp.where(keep, pos, cap - 1)].add(
            jnp.where(keep[:, None], xt[tok_idx], 0.0))
        if r > 1:                                        # duplicate to slots
            buf = jnp.repeat(buf, r, axis=0)             # (e_eff, cap, d)
        # dispatch: (shards, e_loc, cap, d) → a2a over tp axis
        send = buf.reshape(n_exp_shards, e_loc, cap, d)
        recv = jax.lax.all_to_all(send, tp, split_axis=0, concat_axis=0,
                                  tiled=False)
        # recv: (n_exp_shards, e_loc, cap, d) — tokens from every source
        xe = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_exp_shards * cap, d)
        ye = _expert_ffn(xe, wg_ if has_gate else None, wu, wd,
                         cfg.mlp_act)                    # partial over slots
        back = ye.reshape(e_loc, n_exp_shards, cap, d).transpose(1, 0, 2, 3)
        ret = jax.lax.all_to_all(back, tp, split_axis=0, concat_axis=0,
                                 tiled=False)
        ret = ret.reshape(e_eff, cap, d)                 # source-layout buffers
        if r > 1:                                        # sum slot partials
            ret = ret.reshape(e, r, cap, d).sum(axis=1)
        # combine at source: gather each token's k expert outputs
        out_k = ret[flat_e, jnp.clip(pos, 0, cap - 1)]   # (t_loc·k, d)
        out_k = jnp.where(keep[:, None], out_k, 0.0)
        y = (out_k.reshape(t_loc, k, d) *
             gates[..., None].astype(out_k.dtype)).sum(axis=1)
        return y.reshape(b_loc, s_loc, d)

    x_spec = P(dp, tp, None)

    def ew_spec(w):
        """Expert-weight spec: experts sharded over the tp axis. Stacked
        QTensor leaves shard per child (every child leads with E; col_scale
        may be absent/lower-rank, hence per-leaf ranks)."""
        if isinstance(w, QTensor):
            return jax.tree.map(
                lambda a: P(tp, *([None] * (a.ndim - 1))), w)
        return P(tp, None, None)

    y = jax.shard_map(local, mesh=mesh,
                      in_specs=(x_spec, P(None, None), ew_spec(wu_w),
                                ew_spec(wd_w),
                                ew_spec(wg_w) if has_gate else P()),
                      out_specs=x_spec,
                      check_vma=False)(
        xn, p["router"], wu_w, wd_w,
        wg_w if has_gate else jnp.zeros((), xn.dtype))
    return y.astype(x.dtype)


def moe_apply(p, x, cfg: ModelConfig, rules: ShardingRules = NO_RULES, *,
              capture: Optional[dict] = None, prefer_a2a: bool = True) -> jax.Array:
    """Auto-select the execution path (DESIGN.md §4). Packed QTensor expert
    weights (any of wu/wd/wg) ride the a2a path whenever the expert count
    tiles the TP axis directly (slot factor r == 1 — the stacked
    dequant-matmul shards per expert like a dense stack); only the EP×TP
    slot re-layout (r > 1), which reshapes raw weight arrays, forces
    masked-dense for packed experts."""
    packed = any(isinstance(p.get(k), QTensor) for k in ("wu", "wd", "wg"))
    if rules.mesh is None or capture is not None or not prefer_a2a:
        return moe_apply_dense(p, x, cfg, rules, capture=capture)
    b, s, _ = x.shape
    tp = rules.axis_size(rules.tp_axis or ())
    dp = rules.axis_size(rules.batch_axes)
    e = cfg.num_experts
    tileable = (e % tp == 0) or (tp % e == 0 and cfg.d_ff % (tp // e) == 0)
    if packed:
        tileable = e % tp == 0          # packed codes can't slot-split (r>1)
    ok = (tp > 1 and tileable and b % dp == 0
          and s % tp == 0 and (b // dp) * (s // tp) >= 64)
    if ok:
        return moe_apply_a2a(p, x, cfg, rules)
    return moe_apply_dense(p, x, cfg, rules)


# ---------------------------------------------------------------------------
# MoE decoder model: DenseModel with the MLP swapped for the MoE FFN
# ---------------------------------------------------------------------------

import dataclasses
import functools

from repro.models import transformer as T


def moe_block_params(key, cfg: ModelConfig, dtype=jnp.float32):
    ka, km = jax.random.split(key)
    return {"attn": L.attn_params(ka, cfg, dtype),
            "moe": moe_params(km, cfg, dtype)}


def moe_block_apply(p, x, cfg, rules=NO_RULES, *, positions=None, capture=None,
                    kv_cache=None, cache_pos=None, attend_cache=False,
                    table=None, layer=None, fused_decode=False,
                    prefer_a2a=True,
                    attn_chunk: int = 1024, attn_p_dtype=jnp.float32):
    a, new_kv = L.attn_apply(p["attn"], x, cfg, rules, positions=positions,
                             capture=capture, kv_cache=kv_cache,
                             cache_pos=cache_pos, attend_cache=attend_cache,
                             table=table, layer=layer,
                             fused_decode=fused_decode,
                             attn_chunk=attn_chunk,
                             attn_p_dtype=attn_p_dtype)
    x = x + a
    x = x + moe_apply(p["moe"], x, cfg, rules, capture=capture,
                      prefer_a2a=prefer_a2a)
    return x, new_kv


@dataclasses.dataclass
class MoEModel(T.DenseModel):
    """Decoder LM with MoE FFN ([moe] family: qwen3-moe, grok-1)."""
    prefer_a2a: bool = True

    def init(self, key):
        cfg = self.cfg
        k_emb, k_blk, k_head = jax.random.split(key, 3)
        blocks = jax.vmap(lambda k: moe_block_params(k, cfg, self.param_dtype))(
            jax.random.split(k_blk, cfg.num_layers))
        params = {"embed": L.embed_init(k_emb, cfg.padded_vocab, cfg.d_model,
                                        self.param_dtype),
                  "blocks": blocks,
                  "final_norm": jnp.ones((cfg.d_model,), self.param_dtype)}
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(k_head, cfg.d_model,
                                             cfg.padded_vocab, self.param_dtype)
        return params

    def param_logical_axes(self):
        ax = super().param_logical_axes()
        ax["blocks"] = {
            "attn": ax["blocks"]["attn"],
            "moe": moe_logical_axes(self.cfg),
        }
        return ax

    def _block_scan(self, params, h, positions):
        cfg, rules = self.cfg, self.rules
        prefer = self.prefer_a2a
        def body(carry, layer_p):
            y, _ = moe_block_apply(layer_p, carry, cfg, rules,
                                   positions=positions, prefer_a2a=prefer,
                                   attn_chunk=self.attn_chunk,
                                   attn_p_dtype=self.attn_p_dtype)
            return hint(y, rules, ("batch", "tp", None)), None  # seq-parallel carry
        if self.unroll:
            for i in range(cfg.num_layers):
                h, _ = body(h, self.block_slice(params, i))
            return h
        body_fn = jax.checkpoint(body) if self.remat else body
        h, _ = jax.lax.scan(body_fn, h, params["blocks"])
        return h

    def _cached_block(self, positions):
        # prefill (many tokens) uses the a2a path; decode (1 token) the
        # masked-dense path (DESIGN.md §4 MoE path table)
        return functools.partial(
            moe_block_apply, cfg=self.cfg, rules=self.rules,
            prefer_a2a=self.prefer_a2a and positions.shape[1] > 1)

    def block_apply_one(self, params, i, h, *, capture=False):
        cfg = self.cfg
        bp = self.block_slice(params, i)
        cap = {} if capture else None
        b, s = h.shape[0], h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        out, _ = moe_block_apply(bp, h, cfg, self.rules, positions=positions,
                                 capture=cap, prefer_a2a=False)
        return out, (cap or {})

    def block_linears(self, i):
        specs = [
            ("wq", ("blocks", "attn", "wq"), "attn_in"),
            ("wk", ("blocks", "attn", "wk"), "attn_in"),
            ("wv", ("blocks", "attn", "wv"), "attn_in"),
            ("wo", ("blocks", "attn", "wo"), "attn_out_in"),
        ]
        for e in range(self.cfg.num_experts):
            if self.cfg.mlp_act == "silu":
                specs.append((f"moe_wg_{e}", ("blocks", "moe", "wg", e), "moe"))
            specs.append((f"moe_wu_{e}", ("blocks", "moe", "wu", e), "moe"))
            specs.append((f"moe_wd_{e}", ("blocks", "moe", "wd", e), "moe_down"))
        return specs


__all__ = ["moe_params", "moe_logical_axes", "moe_apply", "moe_apply_dense",
           "moe_apply_a2a", "MoEModel", "moe_block_params", "moe_block_apply"]
