"""A block in the shape of Mellum2-12B-A2.5B (JetBrains,
huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct), for the benchmark's
own tests: a mixed-layer expert block entering as one file under
``bench/harness/blocks/`` that no harness file names.

Pre-norm RMSNorm; grouped-query causal attention, windowed in the layers
``layer_types`` calls ``sliding_attention`` (a query reads its last
``sliding_window`` keys, itself among them) and whole in the
``full_attention`` ones; in every layer a sparse MLP: a float32 softmax
router over ``num_experts`` stacked gated-SiLU experts of width
``moe_intermediate_size``, the top ``num_experts_per_tok`` kept and, with
``norm_topk_prob``, renormalized; no shared expert, no bias. Every layer
rotates by the default RoPE at ``rope_theta``; the published full layers'
YaRN belongs to the configuration's own block. The program's tree is the
repo's MoE family's (``repro.models.moe``: ``attn`` and ``moe``); the
program has no window, so below it alone do the two agree. The contract is
in ``bench/harness/blocks/__init__.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from bench.harness.blocks import dense
from bench.harness.cli import BenchError

LAYER_TYPES = ("sliding_attention", "full_attention")


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a mixed-layer expert decoder, under the published
    config's keys."""
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    vocab_size: int
    layer_types: tuple
    sliding_window: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    norm_topk_prob: bool
    rope_theta: float
    rms_norm_eps: float
    max_position_embeddings: int
    tie_word_embeddings: bool

    @classmethod
    def from_config(cls, model: dict) -> "Dims":
        keys = [f.name for f in dataclasses.fields(cls)]
        unread = sorted(set(model) - set(keys))
        if unread:
            raise BenchError(f"the mixed block does not read {unread} of the "
                             f"configuration's model")
        missing = [k for k in keys if k not in model]
        if missing:
            raise BenchError(f"the configuration's model lacks {missing}")
        if model["tie_word_embeddings"]:
            raise BenchError("tie_word_embeddings true: the head's paired "
                             "columns (weights.Recipe) need a head of its own")
        kinds = tuple(model["layer_types"])
        if len(kinds) != model["num_hidden_layers"] or \
                not set(kinds) <= set(LAYER_TYPES):
            raise BenchError(f"layer_types {kinds} is not one of "
                             f"{LAYER_TYPES} for each of the "
                             f"{model['num_hidden_layers']} layers")
        return cls(**dict({k: model[k] for k in keys}, layer_types=kinds))

    def window(self, i: int):
        """The keys a query of layer ``i`` reads at most; None: all."""
        return (self.sliding_window
                if self.layer_types[i] == "sliding_attention" else None)

    def linears(self):
        """One block's linears, stored orientation; the experts' stacked."""
        d, f, e = (self.hidden_size, self.moe_intermediate_size,
                   self.num_experts)
        q, kv = (self.num_attention_heads * self.head_dim,
                 self.num_key_value_heads * self.head_dim)
        return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
                ("wg", d, f, e), ("wu", d, f, e), ("wd", f, d, e)]


# -- seeded floats and the program's tree --------------------------------------

def block_f32(key: jax.Array, dims: Dims) -> dict:
    """Block ``key``'s float32 weights: linears N(0, 1/d_in), the experts'
    stacked (n, d_in, d_out); router (d, experts) N(0, 1/d); norm gains
    1 + N(0, 0.1^2)."""
    out = {}
    for j, (name, d_in, d_out, *n) in enumerate(dims.linears()):
        k = jax.random.fold_in(key, j)
        out[name] = (jax.random.normal(k, (*n, d_in, d_out), jnp.float32)
                     * (1.0 / math.sqrt(d_in)))
    d = dims.hidden_size
    out["router"] = jax.random.normal(
        jax.random.fold_in(key, 50), (d, dims.num_experts),
        jnp.float32) * (1.0 / math.sqrt(d))
    for j, name in enumerate(("attn_norm", "mlp_norm")):
        k = jax.random.fold_in(key, 100 + j)
        out[name] = 1.0 + 0.1 * jax.random.normal(k, (d,), jnp.float32)
    return out


outer_f32 = dense.outer_f32
final_norm = dense.final_norm


def served_block(w: dict, quantize) -> dict:
    """One block's subtree in the MoE family's layout: packed attention
    linears, packed stacked experts, the router and norm gains float32."""
    attn = {n: quantize(w[n]) for n in ("wq", "wk", "wv", "wo")}
    moe = {n: quantize(w[n]) for n in ("wg", "wu", "wd")}
    attn["norm"] = w["attn_norm"]
    moe["norm"], moe["router"] = w["mlp_norm"], w["router"]
    return {"attn": attn, "moe": moe}


def program_config(base, dims: Dims):
    """The program's MoE ModelConfig at the file's sizes (no window)."""
    return dataclasses.replace(
        base, num_layers=dims.num_hidden_layers, d_model=dims.hidden_size,
        num_heads=dims.num_attention_heads,
        num_kv_heads=dims.num_key_value_heads, head_dim=dims.head_dim,
        d_ff=dims.moe_intermediate_size, vocab_size=dims.vocab_size,
        num_experts=dims.num_experts,
        experts_per_token=dims.num_experts_per_tok, mlp_act="silu",
        rope_theta=float(dims.rope_theta), norm_eps=dims.rms_norm_eps)


# -- the plain float32 reference -----------------------------------------------

def layer(w, x, dims: Dims, i: int):
    """Block ``i`` over one sequence x (T, d)."""
    return _layer(w, x, dims, dims.window(i))


@functools.partial(jax.jit, static_argnames=("dims", "window"))
def _layer(w, x, dims: Dims, window):
    t = x.shape[0]
    h, hk, hd = (dims.num_attention_heads, dims.num_key_value_heads,
                 dims.head_dim)
    xn = dense.rms(x, w["attn_norm"], dims.rms_norm_eps)
    q = dense.rotary((xn @ w["wq"]).reshape(t, h, hd), dims.rope_theta)
    k = dense.rotary((xn @ w["wk"]).reshape(t, hk, hd), dims.rope_theta)
    v = (xn @ w["wv"]).reshape(t, hk, hd)
    kh = jnp.repeat(k, h // hk, axis=1)          # head i reads kv i // g
    vh = jnp.repeat(v, h // hk, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, kh) / math.sqrt(hd)
    qpos, kpos = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask = mask & (qpos - kpos < window)
    p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, vh).reshape(t, h * hd)
    x = x + a @ w["wo"]
    xn = dense.rms(x, w["mlp_norm"], dims.rms_norm_eps)
    probs = jax.nn.softmax(xn @ w["router"], axis=-1)          # (T, E)
    top, idx = jax.lax.top_k(probs, dims.num_experts_per_tok)
    if dims.norm_topk_prob:
        top = top / top.sum(axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], idx].set(top)
    up = jnp.einsum("td,edf->etf", xn, w["wu"])
    gate = jnp.einsum("td,edf->etf", xn, w["wg"])
    y = jnp.einsum("etf,efd->etd", dense.act(gate, "silu") * up, w["wd"])
    return x + jnp.einsum("te,etd->td", gates, y)


# -- model operations ------------------------------------------------------------

def decode_flop(dims: Dims, contexts) -> int:
    """One decode token for each live context: attention linears, router
    and the routed experts of every layer, the head, and attention over the
    context, the last ``window`` tokens of it in a windowed layer."""
    d, f = dims.hidden_size, dims.moe_intermediate_size
    q, kv = (dims.num_attention_heads * dims.head_dim,
             dims.num_key_value_heads * dims.head_dim)
    per_layer = (d * q + 2 * d * kv + q * d + d * dims.num_experts
                 + dims.num_experts_per_tok * 3 * d * f)
    per_token = 2 * dims.num_hidden_layers * per_layer \
        + 2 * d * dims.vocab_size
    attn = 0
    for i in range(dims.num_hidden_layers):
        w = dims.window(i)
        keys = sum(contexts if w is None else (min(c, w) for c in contexts))
        attn += 4 * dims.num_attention_heads * dims.head_dim * keys
    return len(contexts) * per_token + attn


def decode_attention(dims: Dims) -> tuple:
    """Every layer runs ``flash_decode``, the sliding ones over a window."""
    return tuple((dims.num_attention_heads, dims.num_key_value_heads,
                  dims.head_dim, dims.window(i))
                 for i in range(dims.num_hidden_layers))
