"""Plain float32 reference of the served dense decoder.

Straightforward ``jax.numpy``, imported from nothing of the program: its own
round-to-nearest INT4 quantizer over the seeded floats of ``weights.py``,
RMSNorm, rotary embedding (half-split, as the published Llama and StarCoder2
code rotate), grouped-query causal attention over the whole sequence, and
the gated SiLU or tanh-GELU MLP. It runs after the window, layer by layer
(one block's floats live at a time) over every sampled sequence, at the
matmul precision it is given: ``highest`` for the reference, ``high`` (three
bf16 passes) for the control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import weights as W

PAD = 256          # rows per head call
SEQ_PAD = 1024     # sequences are right-padded to a multiple of this


def rtn_int4(w: jax.Array, group: int) -> jax.Array:
    """Quantize-dequantize a stored (d_in, d_out) weight onto the
    asymmetric min/max INT4 grid, per output row and group of ``group``
    input columns."""
    wt = w.T
    d_out, d_in = wt.shape
    g = wt.reshape(d_out, d_in // group, group)
    lo = g.min(axis=-1, keepdims=True)
    hi = g.max(axis=-1, keepdims=True)
    scale = jnp.maximum((hi - lo) / 15.0, 1e-8)
    zero = jnp.clip(jnp.round(-lo / scale), 0.0, 15.0)
    q = jnp.clip(jnp.round(g / scale) + zero, 0.0, 15.0)
    return ((q - zero) * scale).reshape(d_out, d_in).T


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


@functools.partial(jax.jit, static_argnames=("dims", "group"))
def dequant_block(key, dims: W.Dims, group: int):
    w = W.block_f32(key, dims)
    return {n: (rtn_int4(v, group) if n in W.LINEARS else v)
            for n, v in w.items()}


@functools.partial(jax.jit, static_argnames=("dims", "q_chunk"))
def layer(w, x, dims: W.Dims, q_chunk: int = 512):
    """One block over one sequence x (T, d)."""
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


@jax.jit
def _embed(table, ids):
    return jnp.take(table, ids, axis=0)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_stats(x, start, targets, gain, head, eps):
    """At the PAD rows of x from ``start``: the best logit, the logit of
    each row's target token, the largest |logit| and the argmax."""
    rows = jax.lax.dynamic_slice_in_dim(x, start, PAD, axis=0)
    lg = rms(rows, gain, eps) @ head
    got = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return (lg.max(axis=-1), got, jnp.abs(lg).max(axis=-1),
            jnp.argmax(lg, axis=-1))


def token_stats(seed: int, dims: W.Dims, recipe: W.Recipe, seqs, starts,
                targets, precision: str = "highest",
                head_precision: str = ""):
    """Reference logits of every sampled sequence at positions
    ``starts[i]`` and after, reduced on the device: ``seqs[i]`` is prompt +
    served tokens but the last (the logits at position j predict token
    j + 1) and ``targets[i]`` one token id per such position. The blocks
    run at ``precision``, the head at ``head_precision`` (by default the
    same). Returns per sequence a dict of host arrays: ``best``, ``got``
    (the target's logit), ``absmax`` and ``argmax``."""
    key = W.base_key(seed)
    outer = jax.jit(W.outer_f32, static_argnames=("dims", "pair_offset"))(
        key, dims, recipe.head_pair_offset)
    with jax.default_matmul_precision(precision):
        hs = []
        for s in seqs:
            ids = np.zeros(-(-len(s) // SEQ_PAD) * SEQ_PAD, np.int32)
            ids[:len(s)] = s
            hs.append(_embed(outer["embed"], jnp.asarray(ids)))
        for i in range(dims.num_hidden_layers):
            w = dequant_block(jax.random.fold_in(key, i), dims,
                              recipe.group_size)
            hs = [layer(w, x, dims) for x in hs]
            del w
    with jax.default_matmul_precision(head_precision or precision):
        out = []
        for x, s, st, tg in zip(hs, seqs, starts, targets):
            n = len(s) - st
            parts = {"best": [], "got": [], "absmax": [], "argmax": []}
            r = 0
            while r < n:
                lo = min(st + r, x.shape[0] - PAD)   # the slice stays inside
                skip = st + r - lo
                take = min(PAD - skip, n - r)
                tgt = np.zeros(PAD, np.int32)
                tgt[skip:skip + take] = tg[r:r + take]
                vals = _head_stats(x, lo, jnp.asarray(tgt),
                                   outer["final_norm"], outer["lm_head"],
                                   dims.rms_norm_eps)
                for k, v in zip(parts, vals):
                    parts[k].append(np.asarray(v)[skip:skip + take])
                r += take
            out.append({k: np.concatenate(v) for k, v in parts.items()})
        return out
