"""Model operations of a dense decoder, from its sizes: what the
algorithm needs per token, counted as two per multiply-add. Recomputed or
padded work does not count."""
from __future__ import annotations

from bench.harness.weights import Dims


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


def decode_flop(dims: Dims, tokens: int, context: int) -> int:
    """``tokens`` decode tokens whose live contexts sum to ``context``."""
    return (tokens * (linear_flop_per_token(dims) + head_flop(dims))
            + attn_flop(dims, context))

