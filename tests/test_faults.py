"""Request lifecycle and failure semantics under deterministic fault
injection: typed submit rejection and load shedding, cancellation and
deadlines, the non-finite-logit decode guard, failure-atomic steps under
injected allocation/spill faults, the engine invariant checker, stall and
deadlock detection, and the chaos acceptance run (seeded FaultPlan on an
overloaded paged engine: invariants hold after every step, every request
terminal, zero leaked pages, survivors bit-identical to fault-free)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny_config
from repro.models import build_model
from repro.serving import (DuplicateRequestError, Engine, EngineConfig,
                           EngineInvariantError, EngineStalledError,
                           FaultPlan, GenerationRequest, InjectedFault,
                           QueueFullError, RequestStatus, SamplingParams,
                           cache_is_finite)

# ---------------------------------------------------------------------------
# shared tiny model (compiles are the dominant test cost)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_lm():
    cfg = get_tiny_config("llama32-1b")
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, lens, gens, base=0, seed=0, **kw):
    rng = np.random.default_rng(seed)
    return [GenerationRequest(
                rid=base + i,
                prompt=rng.integers(1, cfg.vocab_size, size=l).astype(np.int32),
                max_new_tokens=g,
                sampling=SamplingParams(seed=100 + i), **kw)
            for i, (l, g) in enumerate(zip(lens, gens))]


PAGED = dict(num_slots=3, max_len=48, page_size=8, num_pages=9,
             prefix_caching=False)
TRACE = ([7, 9, 11, 7, 9, 13, 7, 9], [10, 12, 9, 11, 8, 10, 12, 9])


def _paged_engine(tiny_lm, faults=None, **over):
    cfg, model, params = tiny_lm
    eng = Engine(model, params, EngineConfig(**{**PAGED, **over}))
    eng.warmup(_requests(cfg, TRACE[0][:2], TRACE[1][:2]))
    if faults is not None:
        eng.set_faults(faults)
    return eng


def _drive(eng, reqs, check_every_step=True, max_steps=2000):
    """Submit, then step with per-step invariant checks → {rid: result}."""
    for r in reqs:
        eng.submit(r)
    for _ in range(max_steps):
        if eng.scheduler.idle:
            break
        eng.step()
        if check_every_step:
            eng.check_invariants()
    assert eng.scheduler.idle, "drive exhausted max_steps"
    out, eng._done = eng._done, []
    return {r.rid: r for r in out}


@pytest.fixture(scope="module")
def paged_baseline(tiny_lm):
    """Fault-free paged run of the shared overload trace — the parity
    oracle every chaos test compares survivors against."""
    cfg, _, _ = tiny_lm
    eng = _paged_engine(tiny_lm)
    out = _drive(eng, _requests(cfg, *TRACE, seed=1))
    assert all(r.status == "ok" for r in out.values())
    assert eng.alloc.pages_in_use == 0
    return out


def _chaos_reqs(cfg):
    return _requests(cfg, *TRACE, seed=1)


# ---------------------------------------------------------------------------
# FaultPlan determinism (no model)
# ---------------------------------------------------------------------------

def test_fault_plan_is_deterministic_and_scripted():
    def draw(plan):
        seq = []
        for step in range(50):
            plan.tick()
            seq.append((plan.fail_alloc(), plan.poison_logits(rid=step % 3)))
        return seq
    a = draw(FaultPlan(seed=7, alloc_fail=0.3, nan_logits=0.1))
    b = draw(FaultPlan(seed=7, alloc_fail=0.3, nan_logits=0.1))
    assert a == b                       # same seed → identical replay
    c = draw(FaultPlan(seed=8, alloc_fail=0.3, nan_logits=0.1))
    assert a != c

    plan = FaultPlan(script=((3, "alloc_fail"), (5, "nan_logits", 2)))
    hits = []
    for step in range(1, 8):
        plan.tick()
        if plan.fail_alloc():
            hits.append(("alloc", step))
        if plan.poison_logits(rid=2):
            hits.append(("nan2", step))
        assert not plan.poison_logits(rid=1)   # rid filter
    assert hits == [("alloc", 3), ("nan2", 5)]

    capped = FaultPlan(seed=0, alloc_fail=1.0, max_faults=2)
    assert sum(capped.fail_alloc() for _ in range(10)) == 2

    vclock = FaultPlan(slow_step_s=0.5)
    t0 = vclock.now()
    vclock.tick()
    vclock.tick()
    assert vclock.now() - t0 == 1.0     # virtual clock, no wall time

    with pytest.raises(ValueError):
        FaultPlan(script=((1, "bogus_kind"),))

    with pytest.raises(InjectedFault):
        FaultPlan(spill_fail=1.0).check_spill()


# ---------------------------------------------------------------------------
# lifecycle: typed rejection, shedding, cancel, deadlines, stall
# ---------------------------------------------------------------------------

def test_duplicate_rid_and_queue_full_raise_typed(tiny_lm):
    cfg, model, params = tiny_lm
    eng = Engine(model, params, EngineConfig(num_slots=1, max_len=32,
                                             max_queue=2))
    eng.warmup(_requests(cfg, [6], [2]))
    rs = _requests(cfg, [6, 6, 6], [2, 2, 2])
    eng.submit(rs[0])
    with pytest.raises(DuplicateRequestError):
        eng.submit(rs[0])               # silently overwriting is a bug
    eng.submit(rs[1])                   # queue now at max_queue=2
    with pytest.raises(QueueFullError):
        eng.submit(rs[2])
    # try_submit converts the shed into a terminal rejected result
    assert eng.try_submit(rs[2]) is False
    with pytest.raises(DuplicateRequestError):
        eng.try_submit(rs[0])           # duplicates still raise
    out = {r.rid: r for r in eng.run()}
    assert out[2].status == "rejected" and out[2].finish_reason == "rejected"
    assert "max_queue" in out[2].error
    assert all(out[i].status == "ok" for i in range(2))
    assert eng.queue_stats()["rejected"] == 1
    assert eng.check_invariants()


def test_finish_reason_length_vs_eos(tiny_lm):
    cfg, model, params = tiny_lm
    eng = Engine(model, params, EngineConfig(num_slots=1, max_len=32))
    eng.warmup(_requests(cfg, [6], [4]))
    probe = _requests(cfg, [6], [4])[0]
    first = {r.rid: r for r in _run_one(eng, probe)}[0].tokens[0]
    again = GenerationRequest(rid=1, prompt=probe.prompt, max_new_tokens=4,
                              sampling=probe.sampling, eos_id=first)
    res = _run_one(eng, again)[0]
    assert res.status == "ok" and res.finish_reason == "eos"
    assert res.tokens == [first]        # stopped at the eos sample
    res2 = _run_one(eng, GenerationRequest(
        rid=2, prompt=probe.prompt, max_new_tokens=4,
        sampling=probe.sampling))[0]
    assert res2.status == "ok" and res2.finish_reason == "length"
    assert len(res2.tokens) == 4


def _run_one(eng, req):
    eng.submit(req)
    return eng.run()


def test_cancel_running_queued_and_unknown(tiny_lm):
    cfg, model, params = tiny_lm
    eng = Engine(model, params, EngineConfig(num_slots=1, max_len=32))
    eng.warmup(_requests(cfg, [6], [4]))
    running, queued = _requests(cfg, [6, 6], [8, 8], base=10)
    eng.submit(running)
    eng.submit(queued)
    eng.step()
    eng.step()                          # running has prefill + decode tokens
    assert eng.cancel(10) and eng.cancel(11)
    assert not eng.cancel(10)           # already terminal
    assert not eng.cancel(424242)       # never submitted
    out = {r.rid: r for r in eng.run()}
    assert out[10].status == "cancelled" and len(out[10].tokens) >= 2
    assert out[11].status == "cancelled" and out[11].tokens == []
    assert eng.check_invariants()


def test_deadline_expires_queued_and_running(tiny_lm):
    cfg, model, params = tiny_lm
    eng = Engine(model, params, EngineConfig(num_slots=1, max_len=32))
    eng.warmup(_requests(cfg, [6], [4]))
    eng.set_faults(FaultPlan(slow_step_s=1.0))   # deterministic clock
    running = _requests(cfg, [6], [20], base=30, deadline_s=5.0)[0]
    queued = _requests(cfg, [6], [4], base=40, deadline_s=3.0)[0]
    eng.submit(running)
    eng.submit(queued)
    out = {r.rid: r for r in eng.run()}
    # the running request kept its pre-deadline tokens; the queued one
    # expired behind it without ever touching a slot
    assert out[30].status == "deadline" and 0 < len(out[30].tokens) < 20
    assert out[40].status == "deadline" and out[40].tokens == []
    assert eng.check_invariants()


def test_stalled_run_raises_typed_with_stuck_state(tiny_lm):
    cfg, model, params = tiny_lm
    eng = Engine(model, params, EngineConfig(num_slots=1, max_len=32))
    eng.warmup(_requests(cfg, [6], [4]))
    eng.submit(_requests(cfg, [6], [20], base=50)[0])
    with pytest.raises(EngineStalledError) as ei:
        eng.run(max_steps=3)
    stuck = ei.value.stuck
    assert [s["rid"] for s in stuck] == [50]
    assert stuck[0]["where"] == "slot 0" and stuck[0]["generated"] > 0
    assert "rid=50" in str(ei.value)
    # the engine is still serviceable: finish the request normally
    out = {r.rid: r for r in eng.run()}
    assert out[50].status == "ok"


def test_deadlock_detected_early_when_pool_never_allocates(tiny_lm):
    cfg, _, _ = tiny_lm
    eng = _paged_engine(tiny_lm, faults=FaultPlan(seed=1, alloc_fail=1.0))
    eng.submit(_requests(cfg, [7], [4])[0])
    with pytest.raises(EngineStalledError) as ei:
        eng.run()                       # patience, not max_steps, trips
    assert "no progress" in str(ei.value)
    assert ei.value.stuck[0]["where"] == "queued"
    assert eng.check_invariants()       # nothing half-admitted


# ---------------------------------------------------------------------------
# decode guard: non-finite logits fail the slot, not the batch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quantized", [False, True], ids=["dense", "int8"])
def test_real_nan_in_cache_fails_only_the_poisoned_slot(tiny_lm, quantized):
    cfg, model, params = tiny_lm
    eng = Engine(model, params, EngineConfig(num_slots=2, max_len=32,
                                             kv_quantized=quantized))
    eng.warmup(_requests(cfg, [6], [4]))
    a, b = _requests(cfg, [6, 6], [8, 8])
    eng.submit(a)
    eng.submit(b)
    eng.step()                          # both prefilled + one decode
    clean = {r.rid: list(r.tokens) for r in eng._results.values()}
    slot = next(s for s in eng.scheduler.active_slots()
                if eng.scheduler.slots[s].request.rid == 0)
    # genuine corruption, not a flag: NaN K entries in the victim's pages
    # (an INT8 pool's scale plane) make its attention, and therefore its
    # logits, non-finite on the next decode step
    pages = jnp.asarray(eng._slot_pages[slot])
    k = eng.kv["k"]
    eng.kv["k"] = (k._replace(scale=k.scale.at[:, pages].set(jnp.nan))
                   if quantized else k.at[:, pages].set(jnp.nan))
    assert not cache_is_finite(eng.kv)  # the diagnostic localizes it
    out = {r.rid: r for r in eng.run()}
    assert out[0].status == "error" and "non-finite" in out[0].error
    assert out[0].tokens == clean[0]    # poisoned-step token not emitted
    assert out[1].status == "ok" and len(out[1].tokens) == 8
    assert eng.check_invariants()


def test_scripted_nan_poison_hits_exact_victim_paged(tiny_lm, paged_baseline):
    cfg, _, _ = tiny_lm
    eng = _paged_engine(tiny_lm,
                        faults=FaultPlan(seed=5, script=((4, "nan_logits",
                                                          2),)))
    out = _drive(eng, _chaos_reqs(cfg))
    bad = [rid for rid, r in out.items() if r.status != "ok"]
    assert bad == [2] and out[2].status == "error"
    for rid, r in out.items():
        if r.status == "ok":
            assert r.tokens == paged_baseline[rid].tokens
    assert eng.alloc.pages_in_use == 0


# ---------------------------------------------------------------------------
# chaos: injected faults under page pressure
# ---------------------------------------------------------------------------

def test_alloc_fault_chaos_keeps_bit_parity(tiny_lm, paged_baseline):
    # allocation faults only reroute scheduling (preempt/resume round-trips
    # are bit-exact), so EVERY request must still match the fault-free run
    cfg, _, _ = tiny_lm
    plan = FaultPlan(seed=7, alloc_fail=0.3)
    eng = _paged_engine(tiny_lm, faults=plan)
    out = _drive(eng, _chaos_reqs(cfg))
    assert all(r.status == "ok" for r in out.values())
    for rid, r in out.items():
        assert r.tokens == paged_baseline[rid].tokens, rid
    assert plan.fired["alloc_fail"] > 0      # the plan actually fired
    assert eng.preemptions > 0               # and forced real preemptions
    assert eng.alloc.pages_in_use == 0


def test_spill_fault_fails_victim_and_survivors_keep_parity(
        tiny_lm, paged_baseline):
    cfg, _, _ = tiny_lm
    plan = FaultPlan(seed=3, alloc_fail=0.3, spill_fail=0.5)
    eng = _paged_engine(tiny_lm, faults=plan)
    out = _drive(eng, _chaos_reqs(cfg))
    errs = {rid for rid, r in out.items() if r.status == "error"}
    assert errs and plan.fired["spill_fail"] > 0
    for rid, r in out.items():
        assert r.status in ("ok", "error")
        if r.status == "ok":
            assert r.tokens == paged_baseline[rid].tokens, rid
        else:
            assert "spill" in r.error or "restore" in r.error
    assert eng.alloc.pages_in_use == 0       # victims leaked nothing


def test_chaos_acceptance_overloaded_paged_engine(tiny_lm, paged_baseline):
    """The acceptance run from the issue: overload + combined fault plan +
    a mid-flight cancel; invariants checked after EVERY step; every request
    reaches a terminal status; zero leaked pages/slots; every ``ok``
    survivor bit-identical to the fault-free run."""
    cfg, _, _ = tiny_lm
    plan = FaultPlan(seed=11, alloc_fail=0.15, spill_fail=0.3,
                     nan_logits=0.01)
    eng = _paged_engine(tiny_lm, faults=plan, max_queue=6)
    reqs = _chaos_reqs(cfg)
    shed = [r.rid for r in reqs if not eng.try_submit(r)]
    assert shed                              # overload actually shed
    cancelled = False
    for _ in range(2000):
        if eng.scheduler.idle:
            break
        eng.step()
        eng.check_invariants()
        if not cancelled and eng.decode_steps >= 3:
            live = eng.scheduler.active_slots()
            if live:
                rid = eng.scheduler.slots[live[-1]].request.rid
                assert eng.cancel(rid)
                cancelled = rid
                eng.check_invariants()
    assert eng.scheduler.idle
    out, eng._done = {r.rid: r for r in eng._done}, []
    assert set(out) == {r.rid for r in reqs}     # every request terminal
    terminal = {s.value for s in RequestStatus} - {"length", "eos"}
    for rid, r in out.items():
        assert r.status in terminal, (rid, r.status)
        assert r.finish_reason != ""
        if r.status == "ok":
            assert r.tokens == paged_baseline[rid].tokens, rid
    assert out[cancelled].status == "cancelled"
    assert {rid: out[rid].status for rid in shed} == \
        {rid: "rejected" for rid in shed}
    assert eng.alloc.pages_in_use == 0
    assert len(eng.scheduler.free) == eng.cfg.num_slots
    assert eng.queue_stats()["rejected"] == len(shed)


# ---------------------------------------------------------------------------
# the invariant checker itself
# ---------------------------------------------------------------------------

def test_invariant_checker_catches_seeded_corruption(tiny_lm):
    cfg, _, _ = tiny_lm
    eng = _paged_engine(tiny_lm)
    for r in _requests(cfg, [7, 9], [8, 8]):
        eng.submit(r)
    eng.step()
    assert eng.check_invariants()
    slot = eng.scheduler.active_slots()[0]
    page = eng._slot_pages[slot][0]
    eng.alloc.decref([page])                 # refcount out from under a slot
    with pytest.raises(EngineInvariantError):
        eng.check_invariants()
    # undo the corruption (white-box) and confirm the checker is satisfied
    eng.alloc._free.remove(page)
    eng.alloc._refs[page] = 1
    assert eng.check_invariants()
    eng.run()


def test_invariant_checker_catches_table_drift(tiny_lm):
    cfg, _, _ = tiny_lm
    eng = _paged_engine(tiny_lm)
    for r in _requests(cfg, [7], [8]):
        eng.submit(r)
    eng.step()
    slot = eng.scheduler.active_slots()[0]
    keep = int(eng._table[slot, 0])
    eng._table[slot, 0] = eng.alloc.num_pages    # block table drifts
    with pytest.raises(EngineInvariantError):
        eng.check_invariants()
    eng._table[slot, 0] = keep
    assert eng.check_invariants()
    eng.run()
