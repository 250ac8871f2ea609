"""The dense decoder block as granite-8b is served: the Llama architecture.

Pre-norm RMSNorm, grouped-query causal attention over the whole sequence
with rotary embedding (half-split, as the published Llama and StarCoder2
code rotate), the gated SiLU MLP (or, with another ``hidden_act``, a plain
tanh-GELU one), no bias on any linear, a head of its own. The contract this
module keeps is in ``bench/harness/blocks/__init__.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from bench.harness.cli import BenchError


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense decoder, under the published config's keys."""
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    vocab_size: int
    hidden_act: str
    rope_theta: float
    rms_norm_eps: float
    max_position_embeddings: int
    tie_word_embeddings: bool

    @classmethod
    def from_config(cls, model: dict) -> "Dims":
        keys = [f.name for f in dataclasses.fields(cls)]
        unread = sorted(set(model) - set(keys))
        if unread:
            raise BenchError(f"the dense block does not read {unread} of the "
                             f"configuration's model")
        missing = [k for k in keys if k not in model]
        if missing:
            raise BenchError(f"the configuration's model lacks {missing}")
        if model["tie_word_embeddings"]:
            raise BenchError("tie_word_embeddings true: the head's paired "
                             "columns (weights.Recipe) need a head of its own")
        return cls(**{k: model[k] for k in keys})

    @property
    def gated(self) -> bool:
        return self.hidden_act == "silu"

    def linears(self):
        """(name, d_in, d_out) of one block's linears, stored orientation."""
        d, f = self.hidden_size, self.intermediate_size
        q, kv = (self.num_attention_heads * self.head_dim,
                 self.num_key_value_heads * self.head_dim)
        out = [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d)]
        if self.gated:
            out.append(("wg", d, f))
        return out + [("wu", d, f), ("wd", f, d)]


# -- seeded floats and the program's tree --------------------------------------

def block_f32(key: jax.Array, dims: Dims) -> dict:
    """Block ``key``'s float32 weights: linears N(0, 1/d_in) in the stored
    (d_in, d_out) orientation, norm gains 1 + N(0, 0.1^2)."""
    out = {}
    for j, (name, d_in, d_out) in enumerate(dims.linears()):
        k = jax.random.fold_in(key, j)
        out[name] = (jax.random.normal(k, (d_in, d_out), jnp.float32)
                     * (1.0 / math.sqrt(d_in)))
    for j, name in enumerate(("attn_norm", "mlp_norm")):
        k = jax.random.fold_in(key, 100 + j)
        out[name] = 1.0 + 0.1 * jax.random.normal(k, (dims.hidden_size,),
                                                  jnp.float32)
    return out


def outer_f32(key: jax.Array, dims: Dims, pair_offset: float) -> dict:
    """Embedding (vocab, d), final norm gain (d,), head (d, vocab), from
    the run's ``weights.base_key``.

    The head's columns come in pairs, 2j and 2j + 1, the second the first
    plus ``pair_offset`` times N(0, 1/d): the two tokens of a pair differ
    in logit by about ``pair_offset`` (N(0, pair_offset^2)), whatever the
    hidden state. So at every position the best token has a near twin, and
    which of the two a run serves depends on how precisely the head's dot
    products were computed, a few ulps of float32 against the rounding of
    fewer bf16 passes. With an offset of 0 the columns are independent."""
    k = jax.random.fold_in(key, 1_000_000)
    ke, kn, kh, kp = jax.random.split(k, 4)
    d, v = dims.hidden_size, dims.vocab_size
    scale = 1.0 / math.sqrt(d)
    first = jax.random.normal(kh, (d, v // 2), jnp.float32) * scale
    twin = first + pair_offset * scale * jax.random.normal(
        kp, (d, v // 2), jnp.float32)
    head = jnp.stack([first, twin], axis=-1).reshape(d, v)
    return {
        "embed": jax.random.normal(ke, (v, d), jnp.float32),
        "final_norm": 1.0 + 0.1 * jax.random.normal(kn, (d,), jnp.float32),
        "lm_head": head,
    }


def served_block(w: dict, quantize) -> dict:
    """One block's subtree in the program's layout (``DenseModel``):
    attention and MLP linears packed by ``quantize``, float32 norm gains."""
    attn = {n: quantize(w[n]) for n in ("wq", "wk", "wv", "wo")}
    mlp = {n: quantize(w[n]) for n in ("wg", "wu", "wd") if n in w}
    attn["norm"], mlp["norm"] = w["attn_norm"], w["mlp_norm"]
    return {"attn": attn, "mlp": mlp}


def program_config(base, dims: Dims):
    """The program's ModelConfig at the benchmark file's sizes."""
    return dataclasses.replace(
        base, num_layers=dims.num_hidden_layers, d_model=dims.hidden_size,
        num_heads=dims.num_attention_heads,
        num_kv_heads=dims.num_key_value_heads, head_dim=dims.head_dim,
        d_ff=dims.intermediate_size, vocab_size=dims.vocab_size,
        mlp_act="silu" if dims.gated else "gelu",
        rope_theta=float(dims.rope_theta), norm_eps=dims.rms_norm_eps)


# -- the plain float32 reference -----------------------------------------------

def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain


def rotary(x, theta):
    """x: (T, H, D); positions 0..T-1; the two halves of D rotate."""
    t, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def act(x, kind):
    if kind == "silu":
        return x * jax.nn.sigmoid(x)
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def layer(w, x, dims: Dims, i: int):
    """Block ``i`` over one sequence x (T, d): every block alike."""
    return _layer(w, x, dims)


@functools.partial(jax.jit, static_argnames=("dims", "q_chunk"))
def _layer(w, x, dims: Dims, q_chunk: int = 512):
    t = x.shape[0]
    h, hk, hd = (dims.num_attention_heads, dims.num_key_value_heads,
                 dims.head_dim)
    xn = rms(x, w["attn_norm"], dims.rms_norm_eps)
    q = rotary((xn @ w["wq"]).reshape(t, h, hd), dims.rope_theta)
    k = rotary((xn @ w["wk"]).reshape(t, hk, hd), dims.rope_theta)
    v = (xn @ w["wv"]).reshape(t, hk, hd)
    kh = jnp.repeat(k, h // hk, axis=1)          # head i reads kv i // g
    vh = jnp.repeat(v, h // hk, axis=1)
    outs = []
    for s in range(0, t, q_chunk):
        qc = q[s:s + q_chunk]
        sc = jnp.einsum("qhd,khd->hqk", qc, kh) / math.sqrt(hd)
        qpos = s + jnp.arange(qc.shape[0])
        mask = jnp.arange(t)[None, :] <= qpos[:, None]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, vh))
    a = jnp.concatenate(outs, axis=0).reshape(t, h * hd)
    x = x + a @ w["wo"]
    xn = rms(x, w["mlp_norm"], dims.rms_norm_eps)
    if dims.gated:
        m = act(xn @ w["wg"], "silu") * (xn @ w["wu"])
    else:
        m = act(xn @ w["wu"], dims.hidden_act)
    return x + m @ w["wd"]


def final_norm(x, outer: dict, dims: Dims):
    return rms(x, outer["final_norm"], dims.rms_norm_eps)


# -- model operations ------------------------------------------------------------
# What the algorithm needs per token, two per multiply-add; recomputed or
# padded work does not count.

def linear_flop_per_token(dims: Dims) -> int:
    """Every block linear, once per token."""
    return 2 * dims.num_hidden_layers * sum(i * o for _, i, o
                                            in dims.linears())


def head_flop(dims: Dims) -> int:
    return 2 * dims.hidden_size * dims.vocab_size


def attn_flop(dims: Dims, keys: int) -> int:
    """q.k and p.v of one query over ``keys`` cached tokens, all layers."""
    return (4 * dims.num_hidden_layers * dims.num_attention_heads
            * dims.head_dim * keys)


def decode_flop(dims: Dims, contexts) -> int:
    """One decode token for each live context in ``contexts``; every layer
    attends the whole context."""
    return (len(contexts) * (linear_flop_per_token(dims) + head_flop(dims))
            + attn_flop(dims, sum(contexts)))


def decode_attention(dims: Dims) -> tuple:
    """Every layer runs ``flash_decode`` over the whole context."""
    return ((dims.num_attention_heads, dims.num_key_value_heads,
             dims.head_dim, None),) * dims.num_hidden_layers
