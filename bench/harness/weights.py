"""Seeded weights and token ids, made by the benchmark and never by the
program under test.

Every float weight of a block comes from :func:`block_f32`, keyed by the run
seed and the block index, so the plain reference can make the same floats
again, block by block, after the program's state is freed. Served weights
are those floats quantized by the program's own packer (round to nearest,
INT4, groups of 128 along the input dimension) inside one jitted call.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

LINEARS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


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

    @classmethod
    def from_config(cls, model: dict) -> "Dims":
        return cls(**{f.name: model[f.name] for f in dataclasses.fields(cls)})

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


@dataclasses.dataclass(frozen=True)
class Recipe:
    """How the seeded weights are made: the INT4 group size, and the
    offset between the head's paired columns (see :func:`outer_f32`)."""
    group_size: int = 128
    head_pair_offset: float = 0.0

    @classmethod
    def from_config(cls, weights: dict) -> "Recipe":
        return cls(int(weights["group_size"]),
                   float(weights.get("head_pair_offset", 0.0)))


def base_key(seed: int) -> jax.Array:
    """A key from a seed of any size (seeds may exceed 32 bits):
    its low and high 32-bit words folded into a fixed key."""
    key = jax.random.key(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


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
    the run's :func:`base_key`.

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


def served_params(seed: int, dims: Dims, recipe: Recipe):
    """The program's parameter tree for serving: every block linear a
    stacked INT4 ``QTensor`` packed by ``QTensor.from_dense`` from
    :func:`block_f32`, embedding and head in float32. One jitted call; a
    loop over blocks keeps one block's floats live at a time."""
    from repro.quant import QTensor

    nl = dims.num_hidden_layers

    def quantize(w):                      # stored (d_in, d_out) → packed
        return QTensor.from_dense(w.T, bits=4, group_size=recipe.group_size)

    def one_block(key0, i):
        w = block_f32(jax.random.fold_in(key0, i), dims)
        attn = {n: quantize(w[n]) for n in ("wq", "wk", "wv", "wo")}
        mlp = {n: quantize(w[n]) for n in ("wg", "wu", "wd") if n in w}
        attn["norm"], mlp["norm"] = w["attn_norm"], w["mlp_norm"]
        return {"attn": attn, "mlp": mlp}

    @jax.jit
    def build(key0):
        shapes = jax.eval_shape(one_block, key0, 0)
        blocks = jax.tree.map(
            lambda s: jnp.zeros((nl,) + s.shape, s.dtype), shapes)

        def body(i, acc):
            new = one_block(key0, i)
            return jax.tree.map(
                lambda a, x: jax.lax.dynamic_update_index_in_dim(a, x, i, 0),
                acc, new)
        blocks = jax.lax.fori_loop(0, nl, body, blocks)
        outer = outer_f32(key0, dims, recipe.head_pair_offset)
        return {"embed": outer["embed"], "blocks": blocks,
                "final_norm": outer["final_norm"],
                "lm_head": outer["lm_head"]}

    return build(base_key(seed))

