"""Operation and byte counts of the kernels and of the model (the dense
block's, ``bench/harness/blocks/dense.py``), checked against hand counts at
small shapes."""
import pytest

import benchutil  # noqa: F401  (the checkout root on the path)

from bench.cost import dequant_matmul, flash_decode
from bench.harness.blocks import dense


def test_dequant_matmul_hand_count():
    # M=2, K=256, N=4, groups of 128: 2*2*256*4 = 4096 operations;
    # packed 4*128 B, scale and zero 2*4*2*4 B, x 2*256*4 B, y 2*4*4 B
    assert dequant_matmul.cost(2, 256, 4, 128) == (
        4096, 4 * 128 + 64 + 2048 + 32)


def test_dequant_matmul_call_shape_from_trace_event():
    shapes = ((16, 14336), (16, 2048), (16, 2048), (14336, 2048),
              (14336, 32), (14336, 32))
    assert dequant_matmul.call_shape(shapes) == (1, (16, 4096, 14336,
                                                      128))


class _Trace:
    def __init__(self, calls, seconds):
        self.calls, self.seconds = calls, seconds

    def kernel_s(self, kernel, program):
        return self.seconds.get(program, 0.0)


def test_dequant_matmul_roofline_share():
    peaks = {"bf16_flop_per_s": 1e12, "hbm_byte_per_s": 1e9}
    shp = ((8, 256), (8, 64), (8, 64), (256, 64), (256, 1), (256, 1))
    flop, moved = dequant_matmul.cost(8, 128, 256, 128)
    ideal = max(flop / 1e12, moved / 1e9)
    tr = _Trace({("decode_fn", "dequant_matmul"): {shp: 3}},
                {"decode_fn": 6 * ideal})
    assert dequant_matmul.roofline_share(tr, ("decode_fn",), peaks) == \
        pytest.approx(50.0)
    assert dequant_matmul.roofline_share(tr, ("prefill_fn",), peaks) is None


def test_flash_decode_hand_count():
    # 3 rows with 10 live tokens in all, 4 heads over 2 KV heads of 8
    flop, moved = flash_decode.cost(10, 3, 4, 2, 8)
    assert flop == 4 * 4 * 8 * 10
    assert moved == 2 * 10 * 2 * 8 * 4 + 2 * 3 * 4 * 8 * 4


DIMS = dense.Dims(hidden_size=8, num_hidden_layers=2, num_attention_heads=2,
                  num_key_value_heads=1, head_dim=4, intermediate_size=16,
                  vocab_size=10, hidden_act="silu", rope_theta=1e4,
                  rms_norm_eps=1e-5, max_position_embeddings=64,
                  tie_word_embeddings=False)


def test_model_flop_hand_count():
    # per block: q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16 each, down 16x8
    per_block = 64 + 32 + 32 + 64 + 3 * 128
    assert dense.linear_flop_per_token(DIMS) == 2 * 2 * per_block
    assert dense.head_flop(DIMS) == 2 * 8 * 10
    assert dense.attn_flop(DIMS, 5) == 4 * 2 * 2 * 4 * 5
    # three decode tokens whose live contexts sum to 12
    assert dense.decode_flop(DIMS, [3, 4, 5]) == (
        3 * (2 * 2 * per_block + 160) + 4 * 2 * 2 * 4 * 12)


def test_non_gated_block_has_six_linears():
    gelu = dense.Dims(**{**DIMS.__dict__, "hidden_act": "gelu_pytorch_tanh"})
    assert [n for n, _, _ in gelu.linears()] == ["wq", "wk", "wv", "wo",
                                                   "wu", "wd"]
    assert len(DIMS.linears()) == 7


def test_decode_attention_is_every_layer():
    assert dense.decode_attention(DIMS) == ((2, 1, 4, None),) * 2
