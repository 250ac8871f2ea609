"""A mixed-layer expert block (``mixed_moe_block.py`` beside this file, in
Mellum2-12B-A2.5B's shape: three sliding layers and one full, window 8,
4 stacked experts, top-2 of a renormalized softmax) enters the benchmark
as one block file: the harness makes its weights, runs its reference and
reads its decode steps with no edit. Its reference is checked against a
hand loop, and the pieces the block contract widened (the layer index,
stacked linears, per-row decode contexts, expert-batched kernel calls)
against hand counts."""
import glob
import json
import os
import shutil
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchutil import ROOT, bench_root, no_persistent_cache  # noqa: F401

from bench import trace_reduce
from bench.cost import dequant_matmul
from bench.harness import cli, reference, serve
from bench.harness import weights as W

BLOCK = "mixed_moe"
CELL = "tiny-mixed.serve.chat"
MODEL = {"hidden_size": 64, "num_hidden_layers": 4,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 256,
         "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
         "sliding_window": 8, "num_experts": 4, "num_experts_per_tok": 2,
         "moe_intermediate_size": 32, "norm_topk_prob": True,
         "rope_theta": 500000.0, "rms_norm_eps": 1e-6,
         "max_position_embeddings": 256, "tie_word_embeddings": False}
GROUP = 32
SEED = 2 ** 35 + 3


@pytest.fixture(scope="module")
def block():
    return cli._module(os.path.join(os.path.dirname(__file__),
                                    "mixed_moe_block.py"), "test_" + BLOCK)


@pytest.fixture(scope="module")
def dims(block):
    return block.Dims.from_config(MODEL)


@pytest.fixture(scope="module")
def floats(block, dims):
    """Block 0's floats on the INT4 grid, as the reference uses them."""
    return reference.dequant_block(jax.random.key(11), block, dims, GROUP)


def hand_layer(w, x, dims, window):
    """One block, token by token and head by head, in float64."""
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    x = np.asarray(x, np.float64)
    t = x.shape[0]
    h, hk, hd = (dims.num_attention_heads, dims.num_key_value_heads,
                 dims.head_dim)

    def rms(v, g):
        return v / np.sqrt(np.mean(v * v) + dims.rms_norm_eps) * g

    def rope(v, pos):                       # v: (hd,)
        half = hd // 2
        ang = pos * dims.rope_theta ** (-np.arange(half) / half)
        a, b = v[:half], v[half:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               a * np.sin(ang) + b * np.cos(ang)])

    xn = np.stack([rms(r, w["attn_norm"]) for r in x])
    q = (xn @ w["wq"]).reshape(t, h, hd)
    k = (xn @ w["wk"]).reshape(t, hk, hd)
    v = (xn @ w["wv"]).reshape(t, hk, hd)
    out = np.zeros((t, h, hd))
    for p in range(t):
        first = 0 if window is None else max(0, p - window + 1)
        for head in range(h):
            g = head // (h // hk)
            qr = rope(q[p, head], p)
            s = np.array([qr @ rope(k[j, g], j)
                          for j in range(first, p + 1)]) / np.sqrt(hd)
            e = np.exp(s - s.max())
            out[p, head] = (e / e.sum()) @ v[first:p + 1, g]
    x = x + out.reshape(t, h * hd) @ w["wo"]
    y = np.zeros_like(x)
    for p in range(t):
        r = rms(x[p], w["mlp_norm"])
        logits = r @ w["router"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs)[:dims.num_experts_per_tok]
        gates = probs[top] / probs[top].sum()
        for gate, e in zip(gates, top):
            a = r @ w["wg"][e]
            m = a / (1.0 + np.exp(-a)) * (r @ w["wu"][e])
            y[p] += gate * (m @ w["wd"][e])
    return x + y


@pytest.mark.parametrize("i", [0, 3], ids=["sliding", "full"])
def test_reference_layer_equals_a_hand_loop(block, dims, floats, i):
    x = jax.random.normal(jax.random.key(5), (20, dims.hidden_size))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(block.layer(floats, x, dims, i))
    want = hand_layer(floats, x, dims, dims.window(i))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_sliding_and_full_layers_differ_past_the_window(block, dims, floats):
    x = jax.random.normal(jax.random.key(6), (20, dims.hidden_size))
    with jax.default_matmul_precision("highest"):
        sliding = np.asarray(block.layer(floats, x, dims, 0))
        full = np.asarray(block.layer(floats, x, dims, 3))
    w = dims.sliding_window
    assert dims.window(0) == w and dims.window(3) is None
    np.testing.assert_array_equal(sliding[:w], full[:w])
    assert np.abs(sliding[w:] - full[w:]).max(axis=-1).min() > 1e-3


def test_stacked_rtn_int4_is_each_experts():
    w3 = jax.random.normal(jax.random.key(7), (4, 64, 32))
    got = np.asarray(reference.rtn_int4(w3, GROUP))
    for e in range(4):
        np.testing.assert_array_equal(
            got[e], np.asarray(reference.rtn_int4(w3[e], GROUP)))
    assert len(np.unique(got[0][:GROUP, 0])) <= 16       # one INT4 group


@pytest.mark.parametrize("per_expert", [False, True],
                         ids=["shared_x", "per_expert_x"])
def test_stacked_quantize_through_expert_apply(per_expert):
    from repro.models.layers import expert_apply
    from repro.quant import QTensor
    w3 = jax.random.normal(jax.random.key(8), (4, 64, 32)) / 8.0
    q = W.quantize(w3, GROUP)
    assert isinstance(q, QTensor) and q.shape == (32, 64)
    assert q.packed.shape == (4, 32, 32) and q.scale.shape == (4, 32, 2)
    for e in range(4):                    # each slice packed as one linear
        one = W.quantize(w3[e], GROUP)
        for a, b in zip((q.packed, q.scale, q.zero),
                        (one.packed, one.scale, one.zero)):
            np.testing.assert_array_equal(np.asarray(a[e]), np.asarray(b))
    deq = reference.rtn_int4(w3, GROUP)
    with jax.default_matmul_precision("highest"):
        if per_expert:
            x = jax.random.normal(jax.random.key(9), (4, 6, 64))
            got = expert_apply(q, x, per_expert=True)
            want = jnp.einsum("etd,edf->etf", x, deq)
        else:
            x = jax.random.normal(jax.random.key(9), (6, 64))
            got = expert_apply(q, x)
            want = jnp.einsum("td,edf->tef", x, deq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# -- per-row decode contexts --------------------------------------------------

PEAKS = {"bf16_flop_per_s": 1e12, "hbm_byte_per_s": 1e9}
ROWS = 4


def decode_context(block, dims, steps, decode_s=1e-3, attn_s=1e-4):
    trace = types.SimpleNamespace(
        program_s=lambda p: decode_s if p == "decode_fn" else 0.0,
        kernel_s=lambda k, p: attn_s if p == "decode_fn" else 0.0)
    return types.SimpleNamespace(
        block=block, dims=dims, trace=trace, steps=steps,
        engine_cfg={"num_slots": ROWS, "kv_layout": "paged",
                    "num_pages": 64, "page_size": 16})


def test_window_clipping_in_the_decode_readers(block, dims):
    # two steps: rows at contexts 3 and 20, then 4 and 21; a window of 8
    steps = [serve.StepRecord((3, 20)), serve.StepRecord((4, 21))]
    ctx = decode_context(block, dims, steps)
    # per token and layer: q 64x64, k and v 64x32 each, o 64x64, router
    # 64x4, two routed experts of 3 x 64x32; the head 64x256
    per_layer = 4096 + 2 * 2048 + 4096 + 256 + 2 * 3 * 2048
    per_token = 2 * 4 * per_layer + 2 * 64 * 256
    sliding = (3 + 8) + (4 + 8)              # min(context, 8), both steps
    full = (3 + 20) + (4 + 21)
    attn = 4 * 4 * 16 * (3 * sliding + full)
    flop = 4 * per_token + attn
    assert block.decode_flop(dims, [3, 20, 4, 21]) == flop
    mfu = cli.reader(ROOT, "mfu.decode")(ctx, PEAKS)
    assert mfu == pytest.approx(100.0 * flop / (1e-3 * 1e12), rel=1e-12)

    def ideal(live):                         # one layer, one step
        flop = 4 * 4 * 16 * live
        moved = 2 * live * 2 * 16 * 4 + 2 * ROWS * 4 * 16 * 4
        return max(flop / 1e12, moved / 1e9)
    want = 3 * (ideal(3 + 8) + ideal(4 + 8)) + ideal(23) + ideal(25)
    got = cli.reader(ROOT, "roofline.flash_decode")(ctx, PEAKS)
    assert got == pytest.approx(100.0 * want / 1e-4, rel=1e-12)


def test_one_pool_needs_one_page_shape(block, dims):
    from bench import program_trace
    ctx = decode_context(block, dims, [])
    assert program_trace.pool_shape(block, dims, ctx.engine_cfg) == (
        64, 16, 2, 16)
    mixed = types.SimpleNamespace(decode_attention=lambda d: (
        (4, 2, 16, 8), (4, 1, 16, None)))
    with pytest.raises(cli.BenchError, match="one page shape"):
        program_trace.pool_shape(mixed, dims, ctx.engine_cfg)


# -- expert-batched kernel calls ------------------------------------------------

def test_batched_call_shape_is_that_many_calls():
    # 4 experts at M 16, K 256, N 128, groups of 128; x shared or per expert
    one = ((16, 128), (16, 128), (16, 128), (128, 128), (128, 2), (128, 2))
    shared = ((4, 16, 128), (16, 128), (16, 128), (4, 128, 128),
              (4, 128, 2), (4, 128, 2))
    own = ((4, 16, 128), (4, 16, 128), (4, 16, 128), (4, 128, 128),
           (4, 128, 2), (4, 128, 2))
    assert dequant_matmul.call_shape(one) == (1, (16, 256, 128, 128))
    assert dequant_matmul.call_shape(shared) == (4, (16, 256, 128, 128))
    assert dequant_matmul.call_shape(own) == (4, (16, 256, 128, 128))

    def share(shapes):
        tr = types.SimpleNamespace(
            calls={("decode_fn", "dequant_matmul"): {shapes: 3}},
            kernel_s=lambda k, p: 1e-3)
        return dequant_matmul.roofline_share(tr, ("decode_fn",), PEAKS)
    flop, moved = dequant_matmul.cost(16, 256, 128, 128)
    unbatched = 100.0 * 3 * max(flop / 1e12, moved / 1e9) / 1e-3
    assert share(one) == pytest.approx(unbatched, rel=1e-12)
    assert share(shared) == pytest.approx(4 * unbatched, rel=1e-12)


def test_vmapped_dequant_wrapper_ops_are_weight_gathers():
    from bench import program_trace as P
    wrap = "jit(decode_fn)/blocks/while/body/closed_call/moe/" \
        "vmap(jit(dequant_matmul))/"
    assert P._weight_gather(P.Instr("fusion", wrap + "gather", (4,), 0))
    assert not P._weight_gather(P.Instr(
        "custom-call", wrap + "dequant_matmul/pallas_call", (4,), 0))


# -- the whole harness, no edit ------------------------------------------------

def add_mixed_cell(root: str) -> None:
    """The block file copied in, and a configuration that names it, a
    workload that uses it: new files and entries only."""
    shutil.copy(os.path.join(os.path.dirname(__file__), "mixed_moe_block.py"),
                os.path.join(root, "bench/harness/blocks", BLOCK + ".py"))
    cfg = json.load(open(os.path.join(root,
                                      "bench/configs/tiny-granite.json")))
    cfg.update(name="tiny-mixed", arch="qwen3-moe-235b-a22b", block=BLOCK,
               model=MODEL)
    cfg["weights"]["group_size"] = GROUP
    with open(os.path.join(root, "bench/configs/tiny-mixed.json"), "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "tiny-mixed", "source": "test",
                             "file": "bench/configs/tiny-mixed.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-mixed",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    json.dump(bench, open(path, "w"))


def test_the_harness_takes_the_mixed_block_with_no_edit(bench_root,
                                                        monkeypatch):
    from repro.quant import QTensor
    add_mixed_cell(bench_root)
    harness = [p for p in glob.glob(os.path.join(bench_root, "bench", "**",
                                                 "*.py"), recursive=True)
               if os.path.basename(p) != BLOCK + ".py"]
    assert not any(BLOCK in open(p).read() for p in harness)

    cell = cli.find_cell(bench_root, CELL)
    block, dims = cell.block, cell.dims
    assert block.__file__.endswith(os.path.join("blocks", BLOCK + ".py"))
    recipe = W.Recipe.from_config(cell.config["weights"])

    # weights: the program's MoE tree, experts stacked over layers and
    # experts, packed slice by slice
    params = W.served_params(SEED, block, dims, recipe)
    model = serve.program_model(block, dims, cell.config["arch"])

    def logical(tree):
        """path -> the stored (..., d_in, d_out) shape of every leaf."""
        def shape(a):
            if isinstance(a, QTensor):
                return a.packed.shape[:a.packed.ndim - 2] + a.shape[::-1]
            return a.shape
        return {jax.tree_util.keystr(p): shape(a) for p, a in
                jax.tree_util.tree_flatten_with_path(
                    tree, is_leaf=lambda a: isinstance(a, QTensor))[0]}
    assert logical(params) == logical(jax.eval_shape(model.init,
                                                     jax.random.key(0)))
    wu = params["blocks"]["moe"]["wu"]
    assert wu.packed.shape[:2] == (4, 4) and wu.shape == (32, 64)

    # reference: past the window, through every layer
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, 256, n).astype(np.int32) for n in (30, 12)]
    starts = [2, 5]
    targets = [rng.integers(0, 256, len(s) - st).astype(np.int32)
               for s, st in zip(seqs, starts)]
    stats = reference.token_stats(SEED, block, dims, recipe, seqs, starts,
                                  targets)
    for st, s, start in zip(stats, seqs, starts):
        assert st["best"].shape == (len(s) - start,)
        assert np.isfinite(st["best"]).all()
        assert (st["got"] <= st["best"]).all()

    # every serving reader, on the recorded scoped trace and two steps
    from test_bench_program_trace import unpacked
    from bench import program_trace as P
    path = unpacked(bench_root, "scoped")
    monkeypatch.setattr(P, "newest", lambda root=P.ROOT: path)
    ctx = serve.ServeContext(block, dims, dict(cell.config["engine"]))
    ctx.trace = trace_reduce.reduce(path)
    ctx.steps = [serve.StepRecord((3, 20)), serve.StepRecord((4, 21))]
    peaks = cli.peaks_for(bench_root, "TPU v5 lite")
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in json.load(open(os.path.join(
            ROOT, "BENCHMARK.json")))["per_layer"]}
    for m in cell.per_layer:
        value = cli.reader(bench_root, m["name"])(ctx, peaks)
        assert value is not None and value >= 0, m["name"]
        assert m["unit"] != "%" or value <= 100, (m["name"], value)


def test_below_the_window_the_program_computes_the_reference(block, dims):
    """The served tree is the program's MoE model's: at a window's length,
    where sliding and full attention read the same keys, the program's
    logits are the reference's."""
    recipe = W.Recipe(GROUP, 1e-5)
    params = W.served_params(SEED, block, dims, recipe)
    model = serve.program_model(block, dims, "qwen3-moe-235b-a22b")
    ids = np.random.default_rng(4).integers(
        0, dims.vocab_size, dims.sliding_window).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(jax.jit(model.logits)(
            params, {"tokens": jnp.asarray(ids)[None]}))[0]
    st, = reference.token_stats(SEED, block, dims, recipe, [ids], [0],
                                [logits.argmax(-1)])
    np.testing.assert_allclose(logits.max(-1), st["best"], rtol=1e-5)
    np.testing.assert_allclose(st["got"], st["best"], rtol=1e-5)
