"""Continuous-batching engine: scheduler invariants, slot-cache
quantization, engine-vs-static greedy parity, per-slot sampling
determinism, and the no-recompilation-after-warmup contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny_config
from repro.models import build_model
from repro.quant import matmul_impl
from repro.serving import (Engine, EngineConfig, GenerationRequest,
                           KVCacheConfig, SamplingParams, Scheduler,
                           cache_bytes, init_slot_cache, kv_dequantize,
                           kv_quantize, kv_update, sample_tokens)
from repro.serving.kv_cache import _reference_dequant
from repro.serving.scheduler import default_buckets


# ---------------------------------------------------------------------------
# shared tiny model (compiles are the dominant test cost)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_lm():
    cfg = get_tiny_config("llama32-1b")
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, lens, gens, rng=None, **sampling):
    rng = rng or np.random.default_rng(0)
    return [GenerationRequest(
                rid=i,
                prompt=rng.integers(1, cfg.vocab_size, size=l).astype(np.int32),
                max_new_tokens=g,
                sampling=SamplingParams(seed=100 + i, **sampling))
            for i, (l, g) in enumerate(zip(lens, gens))]


def _static_step_fns(model):
    from repro.launch.serve import make_step_fns
    return make_step_fns(model)


# ---------------------------------------------------------------------------
# scheduler invariants
# ---------------------------------------------------------------------------

def test_scheduler_fifo_admission_and_slot_reuse():
    s = Scheduler(num_slots=2, max_len=64)
    for i in range(5):
        s.submit(GenerationRequest(rid=i, prompt=np.arange(4, dtype=np.int32),
                                   max_new_tokens=4))
    a0 = s.admit()
    a1 = s.admit()
    assert a0[1].rid == 0 and a1[1].rid == 1          # FIFO order
    assert {a0[0], a1[0]} == {0, 1}                   # distinct slots
    assert s.admit() is None                          # full: no admission
    assert s.num_active == 2 and not s.idle

    freed = a0[0]
    assert s.retire(freed).rid == 0                   # eviction frees slot
    a2 = s.admit()
    assert a2[0] == freed and a2[1].rid == 2          # slot reused, FIFO kept
    for slot in list(s.active_slots()):
        s.retire(slot)
    assert s.admit()[1].rid == 3 and s.admit()[1].rid == 4
    assert s.admit() is None and len(s.queue) == 0


def test_scheduler_rejects_oversized_empty_and_zero_budget_requests():
    s = Scheduler(num_slots=1, max_len=16)
    with pytest.raises(ValueError):
        s.submit(GenerationRequest(rid=0, prompt=np.zeros(10, np.int32),
                                   max_new_tokens=7))   # 10 + 7 > 16
    with pytest.raises(ValueError):
        s.submit(GenerationRequest(rid=1, prompt=np.zeros(0, np.int32),
                                   max_new_tokens=4))
    # a max_new_tokens=0 request would still emit one token (prefill
    # samples unconditionally) — rejected at submit time
    with pytest.raises(ValueError):
        s.submit(GenerationRequest(rid=2, prompt=np.ones(4, np.int32),
                                   max_new_tokens=0))


def test_prompt_bucketing():
    assert default_buckets(64) == (8, 16, 32, 64)
    assert default_buckets(48) == (8, 16, 32, 48)
    s = Scheduler(num_slots=1, max_len=48)
    assert s.bucket_for(1) == 8 and s.bucket_for(8) == 8
    assert s.bucket_for(9) == 16 and s.bucket_for(33) == 48
    s2 = Scheduler(num_slots=1, max_len=64, prompt_buckets=(12, 24))
    assert s2.bucket_for(5) == 12 and s2.bucket_for(13) == 24
    # beyond the largest bucket: admitted (chunked prefill at the largest
    # bucket's width), no longer rejected
    s2.submit(GenerationRequest(rid=9, prompt=np.zeros(30, np.int32),
                                max_new_tokens=4))
    assert s2.bucket_for(30) == 24
    batch = s2.admit_batch()
    assert batch.chunked and batch.bucket == 24
    assert [r.rid for _, r in batch.items] == [9]
    # a bucket wider than max_len would silently clip live prompt tokens at
    # the cache edge — rejected at construction instead
    with pytest.raises(ValueError):
        Scheduler(num_slots=1, max_len=16, prompt_buckets=(8, 32))


def test_scheduler_admit_batch_groups_fifo_head_run():
    s = Scheduler(num_slots=4, max_len=64)          # buckets 8/16/32/64
    lens = [5, 8, 13, 7, 6]                          # buckets 8,8,16,8,8
    for i, l in enumerate(lens):
        s.submit(GenerationRequest(rid=i, prompt=np.ones(l, np.int32),
                                   max_new_tokens=2))
    b0 = s.admit_batch()                             # head-run: rids 0,1
    assert not b0.chunked and b0.bucket == 8
    assert [r.rid for _, r in b0.items] == [0, 1]    # stops at rid 2 (b16)
    b1 = s.admit_batch()
    assert b1.bucket == 16 and [r.rid for _, r in b1.items] == [2]
    b2 = s.admit_batch()                             # free-list caps the run
    assert b2.bucket == 8 and [r.rid for _, r in b2.items] == [3]
    assert s.admit_batch() is None and len(s.queue) == 1
    for slot, _ in b0.items:
        s.retire(slot)
    b3 = s.admit_batch()
    assert [r.rid for _, r in b3.items] == [4]


# ---------------------------------------------------------------------------
# KV cache quantization
# ---------------------------------------------------------------------------

def test_kv_quantize_roundtrip_and_kernel_parity(rng):
    x = jnp.asarray(rng.normal(size=(3, 9, 2, 32)), jnp.float32)
    q = kv_quantize(x, 16)
    deq = _reference_dequant(q, jnp.float32)
    # int8 asymmetric per-group: error bounded by ~scale/2
    assert float(jnp.abs(deq - x).max()) < 0.05
    with matmul_impl("kernel"):                       # interpret-mode Pallas
        deq_k = kv_dequantize(q)
    np.testing.assert_array_equal(np.asarray(deq_k), np.asarray(deq))
    # one-sided/constant groups round-trip (the grid always includes 0, so
    # the zero-point is representable) and zero rows stay exactly zero
    c = jnp.full((1, 1, 1, 32), 3.25)
    qc = kv_quantize(c, 32)
    np.testing.assert_allclose(np.asarray(_reference_dequant(qc, jnp.float32)),
                               3.25, atol=0.02)
    z = kv_quantize(jnp.zeros((1, 1, 1, 32)), 32)
    assert float(jnp.abs(_reference_dequant(z, jnp.float32)).max()) == 0.0


def test_kv_update_scalar_and_vector_writes(rng):
    x = jnp.asarray(rng.normal(size=(2, 3, 2, 16)), jnp.float32)
    base = kv_quantize(jnp.zeros((2, 8, 2, 16)), 16)
    splice = kv_update(base, x, jnp.int32(2))         # scalar: rows 2..4
    got = _reference_dequant(splice, jnp.float32)[:, 2:5]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_reference_dequant(
                                   kv_quantize(x, 16), jnp.float32)))
    tok = x[:, :1]
    scatter = kv_update(base, tok, jnp.asarray([1, 6]))  # per-slot positions
    deq = _reference_dequant(scatter, jnp.float32)
    ref = _reference_dequant(kv_quantize(tok, 16), jnp.float32)
    np.testing.assert_allclose(np.asarray(deq[0, 1]), np.asarray(ref[0, 0]))
    np.testing.assert_allclose(np.asarray(deq[1, 6]), np.asarray(ref[1, 0]))
    assert float(jnp.abs(deq[0, 2:]).max()) == 0.0    # rest untouched


def test_write_slot_batched_matches_sequential(rng, tiny_lm):
    """One batched write_slot dispatch (B rows) is bit-identical to B
    sequential single-slot splices, for dense AND INT8 QuantizedKV storage;
    padding rows (slot == num_slots) are dropped."""
    cfg, _, _ = tiny_lm
    from repro.serving import write_slot
    for quantized in (False, True):
        cache = init_slot_cache(cfg, KVCacheConfig(num_slots=4, max_len=32,
                                                   quantized=quantized))
        kv = {"k": cache["k"], "v": cache["v"]}
        shape = (cfg.num_layers, 3, 8, cfg.num_kv_heads,
                 cfg.resolved_head_dim)
        k_new = jnp.asarray(rng.normal(size=shape), jnp.float32)
        v_new = jnp.asarray(rng.normal(size=shape), jnp.float32)
        slots = jnp.asarray([2, 0, 4])               # 4 == num_slots: pad row
        batched = write_slot(kv, slots, k_new, v_new)
        seq = kv
        for i in (0, 1):                              # pad row never written
            seq = write_slot(seq, jnp.int32(int(slots[i])),
                             k_new[:, i:i + 1], v_new[:, i:i + 1])
        for name in ("k", "v"):
            for a, b in zip(jax.tree.leaves(batched[name]),
                            jax.tree.leaves(seq[name])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_slot_rows_roundtrip(rng, tiny_lm):
    """slot_rows/set_slot_rows (the chunked prefill's working view) slice
    and splice one slot's rows exactly, dense and quantized."""
    cfg, _, _ = tiny_lm
    from repro.serving import set_slot_rows, slot_rows
    for quantized in (False, True):
        cache = init_slot_cache(cfg, KVCacheConfig(num_slots=3, max_len=16,
                                                   quantized=quantized))
        entry = cache["k"]
        row = slot_rows(entry, jnp.int32(1))
        leaves = jax.tree.leaves(row)
        assert all(l.shape[1] == 1 for l in leaves)
        bumped = jax.tree.map(lambda x: x + 1, row)
        back = set_slot_rows(entry, jnp.int32(1), bumped)
        for a, b in zip(jax.tree.leaves(slot_rows(back, jnp.int32(1))),
                        jax.tree.leaves(bumped)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(slot_rows(back, jnp.int32(0))),
                        jax.tree.leaves(slot_rows(entry, jnp.int32(0)))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_int8_cache_bytes_about_half_of_dense(tiny_lm):
    cfg, model, params = tiny_lm
    dense = init_slot_cache(cfg, KVCacheConfig(num_slots=4, max_len=32,
                                               dtype=jnp.bfloat16))
    int8 = init_slot_cache(cfg, KVCacheConfig(num_slots=4, max_len=32,
                                              quantized=True))
    ratio = cache_bytes(dense) / cache_bytes(int8)
    assert 1.5 <= ratio <= 2.0                        # ≈ half the bytes


def test_quantized_cache_logit_tolerance(tiny_lm):
    """INT8 KV cache vs dense through prefill + decode_step: prefill logits
    are exact (attention reads the fresh dense K/V), decode logits are
    within int8 tolerance."""
    cfg, model, params = tiny_lm
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0,
                              cfg.vocab_size)
    dense = model.init_cache(2, 16, jnp.float32)
    quant = init_slot_cache(cfg, KVCacheConfig(num_slots=2, max_len=16,
                                               quantized=True))
    quant["pos"] = jnp.zeros((), jnp.int32)           # static-style scalar pos
    ld, cd = jax.jit(model.prefill)(params, {"tokens": toks}, dense)
    lq, cq = jax.jit(model.prefill)(params, {"tokens": toks}, quant)
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(lq))
    tok = jnp.argmax(ld[:, -1], -1)[:, None]
    dd, _ = jax.jit(model.decode_step)(params, tok, cd)
    dq, _ = jax.jit(model.decode_step)(params, tok, cq)
    scale = float(jnp.abs(dd).max())
    assert float(jnp.abs(dd - dq).max()) < 0.05 * scale


# ---------------------------------------------------------------------------
# engine: greedy parity, slot reuse, no recompilation
# ---------------------------------------------------------------------------

def test_engine_greedy_matches_static_path(tiny_lm, assert_flat_compiles):
    """Acceptance: mixed-length trace through 4 slots (requests > slots, so
    slots get reused mid-run) — every request's greedy output bit-identical
    to the static path, with zero recompilation after warmup."""
    cfg, model, params = tiny_lm
    max_len = 64
    reqs = _requests(cfg, lens=[5, 13, 8, 21, 3, 16, 9, 30],
                     gens=[6, 3, 9, 4, 8, 5, 2, 7])
    engine = Engine(model, params, EngineConfig(num_slots=4, max_len=max_len))
    compiled = engine.warmup(reqs)
    assert compiled["decode"] == 1                    # one program for all slots

    with assert_flat_compiles(engine, compiled):      # no recompilation
        for r in reqs:
            engine.submit(r)
        results = engine.run()
    assert len(results) == len(reqs)
    by_rid = {r.rid: r for r in results}
    from repro.launch.serve import static_greedy_reference
    step_fns = _static_step_fns(model)        # hoisted: compile once
    for req in reqs:
        got = by_rid[req.rid].tokens
        assert len(got) == req.max_new_tokens
        assert got == static_greedy_reference(model, params, req, max_len,
                                              step_fns), req.rid
    assert engine.scheduler.idle
    assert 0.0 < engine.utilization() <= 1.0


def test_engine_warmup_fits_tight_budgets(tiny_lm, assert_flat_compiles):
    """Warmup clones must respect prompt_len + max_new <= max_len even when
    the trace's requests leave no decode headroom (gen=1 at a full-length
    prompt): the clone's budget is clipped and decode still gets compiled
    via the minimal fallback request."""
    cfg, model, params = tiny_lm
    engine = Engine(model, params, EngineConfig(num_slots=2, max_len=16))
    reqs = _requests(cfg, lens=[15, 4], gens=[1, 2])
    compiled = engine.warmup(reqs)                    # must not raise
    assert compiled["decode"] == 1
    with assert_flat_compiles(engine, compiled):
        for r in reqs:
            engine.submit(r)
        results = engine.run()
    assert sorted(len(r.tokens) for r in results) == [1, 2]


def test_engine_burst_admits_in_one_dispatch(tiny_lm, assert_flat_compiles):
    """Acceptance: a burst of B same-bucket requests admits in ONE batched
    prefill dispatch (not B), with greedy outputs still bit-identical to
    the static path and zero recompilation after warmup."""
    cfg, model, params = tiny_lm
    max_len = 64
    reqs = _requests(cfg, lens=[20, 22, 19, 24], gens=[4, 6, 3, 5])  # b32 ×4
    engine = Engine(model, params, EngineConfig(num_slots=4, max_len=max_len))
    compiled = engine.warmup(reqs)
    with assert_flat_compiles(engine, compiled):     # no recompilation
        for r in reqs:
            engine.submit(r)
        results = engine.run()
    assert engine.prefill_dispatches == 1            # one device call for 4
    assert engine.prefill_admitted == len(reqs)
    by_rid = {r.rid: r.tokens for r in results}
    step_fns = _static_step_fns(model)
    from repro.launch.serve import static_greedy_reference
    for req in reqs:
        assert by_rid[req.rid] == static_greedy_reference(
            model, params, req, max_len, step_fns), req.rid


def test_engine_compile_flat_across_burst_sizes(tiny_lm):
    """Warmup pre-compiles the (bucket × pow2-batch-bucket) prefill grid:
    bursts of every size then run with zero new compiles."""
    cfg, model, params = tiny_lm
    engine = Engine(model, params, EngineConfig(num_slots=4, max_len=32))
    compiled = engine.warmup(_requests(cfg, lens=[10], gens=[2]))
    rng = np.random.default_rng(7)
    rid = 0
    for burst in (1, 2, 3, 4):
        reqs = _requests(cfg, lens=[12] * burst, gens=[2] * burst, rng=rng)
        for r in reqs:
            r.rid = rid = rid + 1
            engine.submit(r)
        engine.run()
        assert engine.compile_counts() == compiled, burst
    # 1+2+3+4 requests in 4 dispatches (one per burst: run() drains the
    # queue before the first decode step of each burst)
    assert engine.prefill_dispatches == 4
    assert engine.prefill_admitted == 10


def test_engine_chunked_long_prompt_matches_static_path(
        tiny_lm, assert_flat_compiles):
    """Acceptance: prompts LONGER than the largest bucket stream through
    the bucket-width chunk program and still produce greedy output
    bit-identical to the static path — including slot reuse after a
    long-prompt request (2 slots, 4 requests) — with no compile after
    warmup."""
    cfg, model, params = tiny_lm
    max_len = 64
    reqs = _requests(cfg, lens=[20, 40, 9, 33], gens=[4, 3, 5, 2])
    engine = Engine(model, params,
                    EngineConfig(num_slots=2, max_len=max_len,
                                 prompt_buckets=(8, 16)))
    compiled = engine.warmup(reqs)
    assert compiled["chunk"] == 1                    # one program, ever
    with assert_flat_compiles(engine, compiled):     # no recompilation
        for r in reqs:
            engine.submit(r)
        results = engine.run()
    # ceil(20/16) + ceil(40/16) + ceil(33/16) chunks; rid 2 (9 <= 16) is
    # a normal bucketed admission
    assert engine.chunk_dispatches == 2 + 3 + 3
    assert engine.chunked_admitted == 3
    by_rid = {r.rid: r.tokens for r in results}
    step_fns = _static_step_fns(model)
    from repro.launch.serve import static_greedy_reference
    for req in reqs:
        assert by_rid[req.rid] == static_greedy_reference(
            model, params, req, max_len, step_fns), req.rid


def test_engine_chunked_int8_cache_completes(tiny_lm):
    """Long prompts through the INT8 QuantizedKV slot cache: the chunk
    program quantizes on write and attends the dequantized rows — the
    trace completes with the right budgets and no post-warmup compiles."""
    cfg, model, params = tiny_lm
    reqs = _requests(cfg, lens=[20, 40, 9, 33], gens=[4, 3, 5, 2])
    engine = Engine(model, params,
                    EngineConfig(num_slots=2, max_len=64,
                                 prompt_buckets=(8, 16), kv_quantized=True))
    compiled = engine.warmup(reqs)
    for r in reqs:
        engine.submit(r)
    results = engine.run()
    assert engine.compile_counts() == compiled
    assert sorted(len(r.tokens) for r in results) == sorted(
        r.max_new_tokens for r in reqs)


@pytest.mark.parametrize("layout", ["slots", "paged"])
def test_engine_lowered_programs_run_dots_at_f32(tiny_lm, layout):
    """``lowered_text`` lowers the decode and chunk programs at the engine's
    own shapes without running or recompiling them, and every dot in them
    is traced at float32 precision (``step_jit``), so no row's logits
    depend on how many rows share its batch."""
    cfg, model, params = tiny_lm
    reqs = _requests(cfg, lens=[20, 5], gens=[3, 2])
    engine = Engine(model, params,
                    EngineConfig(num_slots=2, max_len=32,
                                 prompt_buckets=(8, 16), kv_layout=layout,
                                 page_size=8))
    compiled = engine.warmup(reqs)
    for program in ("decode", "chunk"):
        dots = [line for line in engine.lowered_text(program).splitlines()
                if "stablehlo.dot_general" in line]
        assert dots, program
        assert all("precision = [HIGHEST, HIGHEST]" in d for d in dots), \
            program
    assert engine.compile_counts() == compiled
    for r in reqs:
        engine.submit(r)
    assert all(r.ok for r in engine.run())
    assert engine.compile_counts() == compiled


def test_engine_warmup_guards_non_idle(tiny_lm):
    """warmup() drains the scheduler, so calling it with live submissions
    would silently execute and discard them — it must raise instead, and
    a proper warmup-then-submit run returns only caller rids."""
    cfg, model, params = tiny_lm
    engine = Engine(model, params, EngineConfig(num_slots=2, max_len=32))
    reqs = _requests(cfg, lens=[6], gens=[2])
    engine.submit(reqs[0])
    with pytest.raises(RuntimeError):
        engine.warmup(reqs)
    results = engine.run()                           # the real request runs
    assert [r.rid for r in results] == [0]
    engine2 = Engine(model, params, EngineConfig(num_slots=2, max_len=32))
    engine2.warmup(reqs)                             # idle: fine
    engine2.submit(reqs[0])
    assert [r.rid for r in engine2.run()] == [0]     # warmup rids filtered


def test_engine_int8_cache_completes_with_half_bytes(tiny_lm):
    cfg, model, params = tiny_lm
    reqs = _requests(cfg, lens=[5, 13, 8, 21], gens=[6, 3, 9, 4])
    dense = Engine(model, params,
                   EngineConfig(num_slots=4, max_len=64,
                                kv_dtype=jnp.bfloat16))
    int8 = Engine(model, params,
                  EngineConfig(num_slots=4, max_len=64, kv_quantized=True))
    for r in reqs:
        int8.submit(r)
    res = int8.run()
    assert sorted(len(r.tokens) for r in res) == sorted(
        r.max_new_tokens for r in reqs)
    ratio = dense.kv_cache_bytes() / int8.kv_cache_bytes()
    assert 1.5 <= ratio <= 2.0


# ---------------------------------------------------------------------------
# per-slot sampling
# ---------------------------------------------------------------------------

def test_sample_tokens_semantics(rng):
    logits = jnp.asarray(rng.normal(size=(4, 64)) * 3, jnp.float32)
    temps = jnp.asarray([0.0, 1.0, 1.0, 0.7])
    topks = jnp.asarray([0, 0, 5, 1])
    seeds = jnp.asarray([0, 7, 7, 9], jnp.uint32)
    steps = jnp.asarray([0, 3, 3, 1], jnp.uint32)
    a = sample_tokens(logits, temps, topks, seeds, steps)
    b = sample_tokens(logits, temps, topks, seeds, steps)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # deterministic
    assert int(a[0]) == int(jnp.argmax(logits[0]))    # temp 0 → greedy
    assert int(a[3]) == int(jnp.argmax(logits[3]))    # top_k 1 → greedy
    # the key depends on (seed, step), never the slot: permutation-invariant
    perm = jnp.asarray([2, 0, 3, 1])
    c = sample_tokens(logits[perm], temps[perm], topks[perm], seeds[perm],
                      steps[perm])
    np.testing.assert_array_equal(np.asarray(c), np.asarray(a)[np.asarray(perm)])
    # top-k actually truncates: k=5 samples land in the top 5
    many = sample_tokens(jnp.tile(logits[2][None], (32, 1)),
                         jnp.full((32,), 1.5), jnp.full((32,), 5),
                         jnp.arange(32, dtype=jnp.uint32),
                         jnp.zeros((32,), jnp.uint32))
    top5 = set(np.asarray(jax.lax.top_k(logits[2], 5)[1]).tolist())
    assert set(np.asarray(many).tolist()) <= top5


def test_engine_sampling_deterministic_across_runs(tiny_lm):
    """Fixed per-request keys: two engines over the same sampled trace
    produce identical token streams (key = fold_in(seed, token index),
    independent of slot placement)."""
    cfg, model, params = tiny_lm

    def run(slots):
        rng = np.random.default_rng(3)
        reqs = _requests(cfg, lens=[5, 13, 8, 21, 9], gens=[6, 3, 9, 4, 5],
                         rng=rng, temperature=0.9, top_k=8)
        eng = Engine(model, params, EngineConfig(num_slots=slots, max_len=64))
        for r in reqs:
            eng.submit(r)
        return {r.rid: r.tokens for r in eng.run()}

    a = run(slots=4)
    b = run(slots=2)          # different slot layout, same keys
    assert a == b
    assert any(len(set(t)) > 1 or len(t) == 1 for t in a.values())
