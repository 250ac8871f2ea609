"""Paged KV-cache subsystem: allocator/refcount invariants, prefix-cache
match/insert/evict semantics, resume-ticket ordering, paged-vs-slot greedy
bit-parity (dense + INT8), copy-on-write stability of shared-prefix pages,
and preempt-then-resume parity under page-pool pressure."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny_config
from repro.models import build_model
from repro.serving import (Engine, EngineConfig, GenerationRequest,
                           PageAllocator, PrefixCache, SamplingParams,
                           Scheduler, pow2_at_least)
from repro.serving.scheduler import ResumeTicket

from conftest import pool_carry_faults


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


def _run(engine, reqs):
    engine.warmup(reqs)
    for r in reqs:
        engine.submit(r)
    results = engine.run()
    return {r.rid: r.tokens for r in results}


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------

def test_pow2_at_least():
    assert [pow2_at_least(n) for n in (1, 2, 3, 4, 5, 9)] == [1, 2, 4, 4, 8, 16]


def test_page_allocator_refcounts_and_oom():
    a = PageAllocator(4)
    assert a.num_free == 4 and a.pages_in_use == 0
    pages = a.alloc(3)
    assert len(pages) == 3 and len(set(pages)) == 3
    assert a.pages_in_use == 3 and a.peak_in_use == 3
    assert a.alloc(2) is None                  # OOM: all-or-nothing, no leak
    assert a.pages_in_use == 3                 # failed alloc left state alone
    a.incref([pages[0]])
    assert a.refcount(pages[0]) == 2
    assert a.decref([pages[0]]) == 0           # still referenced: not freed
    assert a.decref(pages) == 3                # drops all to zero
    assert a.pages_in_use == 0 and a.num_free == 4
    assert a.peak_in_use == 3                  # high-water mark sticks


def test_page_allocator_double_free_and_incref_on_free_raise():
    a = PageAllocator(2)
    (p,) = a.alloc(1)
    a.decref([p])
    with pytest.raises(ValueError):
        a.decref([p])                          # double free
    with pytest.raises(ValueError):
        a.incref([p])                          # resurrecting a freed page


# ---------------------------------------------------------------------------
# prefix cache
# ---------------------------------------------------------------------------

def test_prefix_cache_match_insert_and_lru_evict():
    a = PageAllocator(8)
    pc = PrefixCache(page_size=4, allocator=a)
    toks = np.arange(12, dtype=np.int32)

    assert pc.match(toks) == ([], 0)           # cold: no pages
    pages = a.alloc(3)
    pc.insert(toks, pages)                     # caches pages 0,1,2 (12 // 4)
    assert a.refcount(pages[0]) == 2           # cache holds its own ref
    a.decref(pages)                            # requester done: cache keeps 1

    # full-prefix match is capped at (len-1)//page_size: the last token
    # must run through prefill so the request samples its first output
    hit, n = pc.match(toks)
    assert hit == pages[:2] and n == 8
    assert a.refcount(pages[0]) == 2           # match increfs for the caller
    a.decref(hit)

    # divergence mid-prefix only matches the shared pages
    fork = toks.copy()
    fork[5] = 99
    hit, n = pc.match(fork)
    assert hit == pages[:1] and n == 4
    a.decref(hit)

    # eviction walks LRU order but only takes refcount-1 (unshared) pages
    held = pc.match(toks)[0]                   # pin pages 0,1
    assert pc.evict(need=3) >= 1               # page 2 is evictable
    assert a.refcount(pages[2]) == 0
    assert a.refcount(pages[0]) == 2           # pinned pages survived
    a.decref(held)

    pc.clear()
    assert a.pages_in_use == 0


def test_prefix_cache_insert_is_idempotent_on_shared_pages():
    a = PageAllocator(8)
    pc = PrefixCache(page_size=4, allocator=a)
    toks = np.arange(12, dtype=np.int32)
    pages = a.alloc(3)
    assert pc.insert(toks, pages) == 3
    a.decref(pages)
    assert a.refcount(pages[0]) == 1           # cache's own reference
    # a second request with the same prompt re-inserts the same hashes:
    # existing entries are touched, not re-counted
    hit, _ = pc.match(toks)                    # pages[:2], +1 ref each
    assert pc.insert(toks, hit + pages[2:]) == 0
    assert a.refcount(pages[0]) == 2           # cache(1) + match(1), no creep
    assert a.refcount(pages[2]) == 1           # cache only (match was capped)
    a.decref(hit)
    pc.clear()
    assert a.pages_in_use == 0


# ---------------------------------------------------------------------------
# scheduler: resume tickets
# ---------------------------------------------------------------------------

def test_resume_ticket_ordering_and_admission():
    s = Scheduler(num_slots=1, max_len=64)
    reqs = [GenerationRequest(rid=i, prompt=np.ones(4, np.int32),
                              max_new_tokens=4) for i in range(3)]
    for r in reqs:
        s.submit(r)
    slot, r0 = s.admit()
    s.slots[slot].generated = 2
    t0 = ResumeTicket(request=r0, generated=2, last_token=7, pos=5, n_pages=1)
    s.preempt(slot, t0)
    # the ticket outranks every never-admitted request (r0.seq is oldest)
    assert s.peek() is t0
    # batched admission never pops a ticket — the engine must restore pages
    assert s.admit_batch() is None
    slot, head = s.admit_head()
    assert head is t0
    assert s.slots[slot].generated == 2        # decode progress survives

    # a second, younger ticket queues BEHIND the older one
    t1 = ResumeTicket(request=reqs[1], generated=1, last_token=3, pos=5,
                      n_pages=1)
    s.preempt(slot, t0)                        # r0 back at the head
    s.requeue(t1)
    assert s.peek() is t0 and s.queue[1] is t1
    assert isinstance(s.queue[2], GenerationRequest)


# ---------------------------------------------------------------------------
# engine parity: paged vs slot
# ---------------------------------------------------------------------------

def test_paged_matches_slot_greedy_dense(tiny_lm, assert_flat_compiles):
    """Acceptance: mixed-length greedy trace through the paged engine is
    bit-identical to the slot engine (page_size divides max_len)."""
    cfg, model, params = tiny_lm
    max_len = 64
    reqs = _requests(cfg, lens=[5, 13, 8, 21, 3, 16], gens=[6, 3, 9, 4, 8, 5])
    slot = Engine(model, params, EngineConfig(num_slots=4, max_len=max_len))
    want = _run(slot, reqs)
    paged = Engine(model, params, EngineConfig(
        num_slots=4, max_len=max_len, kv_layout="paged", page_size=8))
    compiled = paged.warmup(reqs)
    with assert_flat_compiles(paged, compiled):  # no recompilation after warmup
        for r in reqs:
            paged.submit(r)
        got = {r.rid: r.tokens for r in paged.run()}
    for req in reqs:
        assert got[req.rid] == want[req.rid], req.rid
    assert paged.alloc.pages_in_use == paged.page_stats()["prefix_cached_pages"]
    assert paged.scheduler.idle


def test_paged_int8_matches_slot_int8(tiny_lm):
    """The paged pool with per-page INT8 scales reproduces the slot
    engine's INT8 outputs exactly — same quantizer, same group shapes."""
    cfg, model, params = tiny_lm
    max_len = 32
    reqs = _requests(cfg, lens=[6, 11, 9], gens=[5, 4, 6])
    slot = Engine(model, params, EngineConfig(
        num_slots=2, max_len=max_len, kv_quantized=True))
    want = _run(slot, reqs)
    paged = Engine(model, params, EngineConfig(
        num_slots=2, max_len=max_len, kv_quantized=True,
        kv_layout="paged", page_size=8))
    got = _run(paged, reqs)
    for req in reqs:
        assert got[req.rid] == want[req.rid], req.rid


def test_paged_prefix_hit_reuses_pages_copy_free(tiny_lm):
    """Requests sharing a prompt prefix reuse the cached pages (no copy):
    hits are counted, reused tokens skip prefill, the shared pages' bytes
    are untouched by the diverging request (CoW by construction), and
    outputs stay bit-identical to the slot engine."""
    cfg, model, params = tiny_lm
    max_len, pg = 64, 8
    rng = np.random.default_rng(3)
    prefix = rng.integers(1, cfg.vocab_size, size=24).astype(np.int32)
    reqs = []
    for i in range(4):
        tail = rng.integers(1, cfg.vocab_size, size=6).astype(np.int32)
        reqs.append(GenerationRequest(rid=i,
                                      prompt=np.concatenate([prefix, tail]),
                                      max_new_tokens=5,
                                      sampling=SamplingParams(seed=100 + i)))
    slot = Engine(model, params, EngineConfig(num_slots=2, max_len=max_len))
    want = _run(slot, reqs)

    paged = Engine(model, params, EngineConfig(
        num_slots=2, max_len=max_len, kv_layout="paged", page_size=pg))
    paged.warmup(reqs)
    paged.submit(reqs[0])
    first = {r.rid: r.tokens for r in paged.run()}
    # the finished request left its full-page prefix in the cache
    shared, n_tok = paged.prefix.match(reqs[1].prompt)
    assert n_tok >= len(prefix) - pg           # ≥ the shared full pages
    snap = np.asarray(paged.kv["k"][:, shared])
    paged.alloc.decref(shared)

    for r in reqs[1:]:
        paged.submit(r)
    rest = {r.rid: r.tokens for r in paged.run()}
    stats = paged.page_stats()
    assert stats["prefix_hits"] == 3 and stats["prefix_misses"] == 1
    assert stats["prefix_hit_tokens"] >= 3 * (len(prefix) - pg)
    # shared pages byte-stable: diverging requests wrote only fresh pages
    np.testing.assert_array_equal(np.asarray(paged.kv["k"][:, shared]), snap)
    got = {**first, **rest}
    for req in reqs:
        assert got[req.rid] == want[req.rid], req.rid


def test_paged_preempt_then_resume_matches_slot(tiny_lm):
    """Acceptance: an oversubscribed pool (num_pages < slots*pages_per_slot)
    forces preemption mid-decode; spilled requests resume from restored
    pages and still produce bit-identical greedy output."""
    cfg, model, params = tiny_lm
    max_len = 48                               # 6 pages/slot at page_size 8
    reqs = _requests(cfg, lens=[30, 29, 31, 28], gens=[12, 12, 12, 12])
    slot = Engine(model, params, EngineConfig(num_slots=3, max_len=max_len))
    want = _run(slot, reqs)
    paged = Engine(model, params, EngineConfig(
        num_slots=3, max_len=max_len, kv_layout="paged", page_size=8,
        num_pages=9, prefix_caching=False))    # 9 < 3*6: decode must evict
    got = _run(paged, reqs)
    stats = paged.page_stats()
    assert stats["preemptions"] > 0 and stats["resumes"] > 0
    assert stats["pages_spilled"] > 0
    assert stats["peak_pages_in_use"] <= 9
    for req in reqs:
        assert got[req.rid] == want[req.rid], req.rid
    assert paged.alloc.pages_in_use == 0       # everything returned


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "int8"])
@pytest.mark.parametrize("name", ["decode", "chunk"])
def test_paged_programs_keep_pools_in_place(tiny_lm, name, quant):
    """The paged decode and chunk programs write and read the stacked pools
    at each layer's index: no op slices, copies or writes back a pool (the
    token scatter aside), and every pool plane aliases input to output.
    Decode reads through the page gather here: the fused kernel's
    interpret-mode discharge moves its operands on the CPU, and its TPU
    program is checked in ``test_chip_compile``."""
    cfg, model, params = tiny_lm
    eng = Engine(model, params, EngineConfig(
        num_slots=2, max_len=32, prompt_buckets=(8, 16), kv_layout="paged",
        page_size=8, num_pages=11, use_fused_decode=False,
        **(dict(kv_quantized=True, kv_dtype=jnp.bfloat16) if quant else {})))
    fn, args = {"decode": (eng._decode, eng._decode_args),
                "chunk": (eng._chunk, eng._dummy_chunk_args)}[name]
    hlo = fn.lower(*args()).compile().as_text()
    assert pool_carry_faults(eng, hlo) == []


def test_paged_pool_must_fit_one_request(tiny_lm):
    cfg, model, params = tiny_lm
    with pytest.raises(ValueError):
        Engine(model, params, EngineConfig(
            num_slots=2, max_len=64, kv_layout="paged", page_size=8,
            num_pages=7))                      # < pages_per_slot (8)


# ---------------------------------------------------------------------------
# mixed-bucket admission
# ---------------------------------------------------------------------------

def test_mixed_admission_one_dispatch_same_tokens(tiny_lm):
    """mixed=True admits a short/long interleave in ONE right-padded
    prefill dispatch (vs one per bucket flip) with bit-identical output."""
    cfg, model, params = tiny_lm
    max_len = 64
    lens = [5, 13, 6, 20]                      # buckets 8,16,8,32: 4 flips
    reqs = _requests(cfg, lens=lens, gens=[4, 5, 6, 3])
    plain = Engine(model, params, EngineConfig(num_slots=4, max_len=max_len))
    want = _run(plain, reqs)
    assert plain.prefill_dispatches == 4       # one per bucket flip
    mixed = Engine(model, params, EngineConfig(
        num_slots=4, max_len=max_len, mixed_admission=True))
    got = _run(mixed, reqs)
    assert mixed.prefill_dispatches == 1       # the whole head-run at once
    for req in reqs:
        assert got[req.rid] == want[req.rid], req.rid


# ---------------------------------------------------------------------------
# preemption edge cases (lifecycle hardening)
# ---------------------------------------------------------------------------

def test_preempt_sole_active_request_resumes_bit_identically(tiny_lm):
    """Preempting the ONLY active request (impossible organically — the
    oldest is never victimized — but reachable via operator action) must
    fully round-trip: ticket queued, pool drained, then resumed onto a
    fresh slot with bit-identical continuation."""
    cfg, model, params = tiny_lm
    req = _requests(cfg, lens=[13], gens=[10])[0]
    slot = Engine(model, params, EngineConfig(num_slots=2, max_len=32))
    want = _run(slot, req and [req])[0]

    paged = Engine(model, params, EngineConfig(
        num_slots=2, max_len=32, kv_layout="paged", page_size=8,
        prefix_caching=False))
    paged.warmup([req])
    paged.submit(req)
    paged.step()
    paged.step()                                # prefill + a decode step
    live = paged.scheduler.active_slots()
    assert live == [0] or len(live) == 1
    paged._preempt(live[0])                     # white-box: sole survivor
    assert paged.alloc.pages_in_use == 0        # everything spilled out
    assert paged.scheduler.num_active == 0
    paged.check_invariants()
    got = {r.rid: r for r in paged.run()}[0]
    assert got.status == "ok" and got.tokens == want
    assert paged.preemptions == 1 and paged.resumes == 1
    assert paged.alloc.pages_in_use == 0


def test_resume_waits_until_pool_frees_pages(tiny_lm):
    """A ticket whose pages no longer fit the free pool must WAIT (never
    preempt a strictly-older request) and resume bit-identically once the
    older request retires and frees its pages."""
    cfg, model, params = tiny_lm
    # pg=4, max_len=16 → 4 pages/slot; pool of 6 pages, 2 slots. Both
    # requests grow from 2 to 4 pages, so A's extension at pos 12 must
    # preempt B, and B's 3-page ticket can't fit until A retires.
    reqs = _requests(cfg, lens=[7, 7], gens=[9, 9])
    slot = Engine(model, params, EngineConfig(num_slots=2, max_len=16))
    want = _run(slot, reqs)

    paged = Engine(model, params, EngineConfig(
        num_slots=2, max_len=16, kv_layout="paged", page_size=4,
        num_pages=6, prefix_caching=False))
    paged.warmup(reqs)
    for r in reqs:
        paged.submit(r)
    waited = 0
    got = {}
    for _ in range(200):
        if paged.scheduler.idle:
            break
        paged.step()
        paged.check_invariants()
        head = paged.scheduler.peek()
        if isinstance(head, ResumeTicket) and paged.scheduler.num_active:
            waited += 1                         # ticket parked behind elder
    assert paged.scheduler.idle
    got = {r.rid: r.tokens for r in paged._done}
    assert paged.preemptions >= 1 and paged.resumes >= 1
    assert waited >= 1                          # the wait actually happened
    for r in reqs:
        assert got[r.rid] == want[r.rid], r.rid
    assert paged.alloc.pages_in_use == 0


def test_cancel_while_spilled_frees_ticket_and_payload(tiny_lm):
    """Cancelling a preempted (spilled) request drops its ticket from the
    queue, emits its pre-preemption partial tokens, and leaves the pool
    clean — the host payload dies with the ticket."""
    cfg, model, params = tiny_lm
    reqs = _requests(cfg, lens=[13, 13], gens=[8, 8])
    paged = Engine(model, params, EngineConfig(
        num_slots=2, max_len=32, kv_layout="paged", page_size=8,
        prefix_caching=False))
    paged.warmup(reqs)
    for r in reqs:
        paged.submit(r)
    paged.step()
    paged.step()
    victim = paged.scheduler.active_slots()[-1]
    rid = paged.scheduler.slots[victim].request.rid
    pre_tokens = list(paged._results[rid].tokens)
    paged._preempt(victim)                      # spill to a host ticket
    assert isinstance(paged.scheduler.peek(), ResumeTicket)
    assert paged.cancel(rid)
    assert paged.scheduler.peek() is None or not isinstance(
        paged.scheduler.peek(), ResumeTicket)   # ticket gone from the queue
    paged.check_invariants()
    out = {r.rid: r for r in paged.run()}
    assert out[rid].status == "cancelled"
    assert out[rid].tokens == pre_tokens        # partial tokens survive
    other = [r for r in out.values() if r.rid != rid][0]
    assert other.status == "ok" and len(other.tokens) == 8
    assert paged.alloc.pages_in_use == 0
    assert paged.resumes == 0                   # the ticket never resumed
