"""Telemetry subsystem: metrics registry semantics (histogram buckets,
label handling, ring-buffer gauge traces, exposition round-trip), request
trace lifecycle invariants for every terminal status, exact span durations
under the FaultPlan virtual clock, and the decode-loop overhead guard
(metrics on vs off: bit-identical tokens, flat compile counts, identical
dispatch counts)."""
import jax
import numpy as np
import pytest

from repro.configs import get_tiny_config
from repro.models import build_model
from repro.obs import (LATENCY_BUCKETS, MetricsRegistry, RingBuffer, Trace,
                       TraceError, hist_quantile, parse_exposition,
                       snapshot_series)
from repro.serving import (Engine, EngineConfig, FaultPlan,
                           GenerationRequest, RequestStatus, SamplingParams)

# ---------------------------------------------------------------------------
# registry unit tests (no model, no jax dispatch)
# ---------------------------------------------------------------------------

def test_counter_labels_and_kind_mismatch():
    reg = MetricsRegistry()
    fam = reg.counter("widgets_total", "w", labelnames=("kind",))
    fam.labels(kind="a").inc()
    fam.labels(kind="a").inc(2)
    fam.labels(kind="b").inc(5)
    snap = reg.snapshot()
    assert snapshot_series(snap, "counters", "widgets_total",
                           {"kind": "a"})["value"] == 3
    assert snapshot_series(snap, "counters", "widgets_total",
                           {"kind": "b"})["value"] == 5
    # get-or-create returns the same family; a kind clash raises
    assert reg.counter("widgets_total", "w", labelnames=("kind",)) is fam
    with pytest.raises((ValueError, TypeError)):
        reg.gauge("widgets_total", "w", labelnames=("kind",))
    # the labelname set is validated exactly
    with pytest.raises((ValueError, KeyError, TypeError)):
        fam.labels(wrong="a")
    with pytest.raises((ValueError, KeyError, TypeError)):
        fam.labels()


def test_gauge_peak_mean_and_ring_trace():
    reg = MetricsRegistry()
    g = reg.gauge("depth", "d", labelnames=(), trace_capacity=3).labels()
    for v in (1, 5, 2, 4):
        g.set(v)
    assert g.peak == 5
    assert g.mean == pytest.approx(3.0)
    assert g.samples == 4
    assert [int(v) for v in g.trace_values()] == [5, 2, 4]   # ring keeps tail
    assert g.trace_dropped == 1
    # set_value refreshes value/peak without recording a sample
    g.set_value(9)
    assert g.peak == 9 and g.samples == 4


def test_ring_buffer_keeps_most_recent():
    rb = RingBuffer(2)
    for v in range(5):
        rb.append(v)
    assert list(rb.values()) == [3, 4]
    assert rb.dropped == 3


def test_histogram_bucket_semantics_and_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "l", buckets=(0.1, 1.0, 10.0),
                      labelnames=()).labels()
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):                    # 50 overflows
        h.observe(v)
    snap = reg.snapshot()
    e = snapshot_series(snap, "histograms", "lat")
    assert e["le"] == [0.1, 1.0, 10.0]
    assert e["counts"] == [1, 2, 1, 1]                       # +overflow slot
    assert e["count"] == 5
    assert e["sum"] == pytest.approx(56.05)
    assert e["min"] == 0.05 and e["max"] == 50.0
    # quantiles interpolate within buckets, clamp to observed extremes
    assert hist_quantile(e, 0.0) >= e["min"]
    assert hist_quantile(e, 1.0) == e["max"]                 # overflow -> max
    assert e["min"] <= hist_quantile(e, 0.5) <= 1.0


def test_latency_buckets_are_log_spaced_and_cover_serving_range():
    assert LATENCY_BUCKETS[0] == pytest.approx(1e-4)
    assert LATENCY_BUCKETS[-1] >= 1000
    ratios = [b / a for a, b in zip(LATENCY_BUCKETS, LATENCY_BUCKETS[1:])]
    assert all(r == pytest.approx(10 ** (1 / 3), rel=1e-4) for r in ratios)


def test_disabled_registry_skips_histograms_but_counts():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c_total", "c", labelnames=()).labels()
    h = reg.histogram("h", "h", buckets=(1.0,), labelnames=()).labels()
    c.inc(3)
    h.observe(0.5)
    snap = reg.snapshot()
    assert snapshot_series(snap, "counters", "c_total")["value"] == 3
    assert snapshot_series(snap, "histograms", "h")["count"] == 0


def test_exposition_round_trip():
    reg = MetricsRegistry()
    reg.counter("req_total", "r", labelnames=("status",)) \
       .labels(status="ok").inc(7)
    g = reg.gauge("depth", "d", labelnames=()).labels()
    g.set(4)
    h = reg.histogram("lat", "l", buckets=(1.0, 10.0), labelnames=()).labels()
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_prometheus()
    parsed = parse_exposition(text)
    assert parsed["req_total"][(("status", "ok"),)] == 7
    assert parsed["depth"][()] == 4
    # histogram buckets are cumulative with a +Inf terminal
    b = parsed["lat_bucket"]
    assert b[(("le", "1"),)] == 1                   # compact float format
    assert b[(("le", "10"),)] == 2
    assert b[(("le", "+Inf"),)] == 2
    assert parsed["lat_count"][()] == 2
    assert parsed["lat_sum"][()] == pytest.approx(5.5)


# ---------------------------------------------------------------------------
# trace lifecycle invariants (no model)
# ---------------------------------------------------------------------------

def _trace(events, rid=0):
    tr = Trace(rid, events[0][1])
    assert events[0][0] == "submit"
    for name, t in events[1:]:
        tr.stamp(name, t)
    return tr


def test_trace_valid_shapes_for_every_terminal_status():
    shapes = {
        "ok": [("submit", 0), ("admitted", 1), ("first_token", 1),
               ("end:ok", 3)],
        "length": [("submit", 0), ("admitted", 1), ("first_token", 2),
                   ("end:length", 4)],
        "eos": [("submit", 0), ("admitted", 1), ("first_token", 1),
                ("end:eos", 2)],
        "cancelled": [("submit", 0), ("end:cancelled", 1)],
        "deadline": [("submit", 0), ("admitted", 1), ("end:deadline", 3)],
        "rejected": [("submit", 0), ("end:rejected", 0)],
        "error": [("submit", 0), ("admitted", 1), ("first_token", 1),
                  ("preempt", 2), ("resume", 3), ("end:error", 4)],
    }
    assert set(shapes) == {s.value for s in RequestStatus}
    for status, events in shapes.items():
        tr = _trace(events)
        assert tr.validate() and tr.done and tr.status == status
        spans = tr.spans()
        # spans are ordered and sit inside the trace window
        starts = [s.start for s in spans]
        assert starts == sorted(starts)
        for s in spans:
            assert events[0][1] <= s.start <= s.end <= events[-1][1]
    pre = _trace(shapes["error"]).spans()
    assert [s.name for s in pre] == ["queued", "prefill", "decode",
                                    "preempted"]
    assert pre[-1].duration == 1


def test_trace_preempt_without_resume_closes_at_end():
    tr = _trace([("submit", 0), ("admitted", 1), ("first_token", 1),
                 ("preempt", 2), ("end:cancelled", 5)])
    assert tr.validate()
    pspan = [s for s in tr.spans() if s.name == "preempted"]
    assert len(pspan) == 1 and pspan[0].duration == 3


def test_trace_invariant_violations_raise():
    bad = [
        [("admitted", 0), ("end:ok", 1)],                    # no submit
        [("submit", 0), ("first_token", 1), ("end:ok", 2)],  # no admitted
        [("submit", 0), ("admitted", 2), ("first_token", 1),
         ("end:ok", 3)],                                     # not monotone
        [("submit", 0), ("admitted", 1), ("end:ok", 2)],     # ok w/o token
        [("submit", 0), ("admitted", 1), ("end:rejected", 2)],
        [("submit", 0), ("preempt", 1), ("end:cancelled", 2)],
        [("submit", 0), ("admitted", 1), ("first_token", 1),
         ("preempt", 2), ("preempt", 3), ("end:error", 4)],  # nested
        [("submit", 0), ("admitted", 1), ("first_token", 1),
         ("resume", 2), ("end:error", 3)],                   # dangling resume
        [("submit", 0), ("end:ok", 1), ("end:ok", 2)],       # two terminals
        [("submit", 0), ("submit", 1), ("end:cancelled", 2)],
        [("submit", 0), ("end:wat", 1)],                     # unknown status
        [("submit", 0)],                                     # never terminal
    ]
    for events in bad:
        tr = Trace(0, events[0][1])
        tr.events[0] = (events[0][0], float(events[0][1]))
        for name, t in events[1:]:
            tr.stamp(name, t)
        with pytest.raises(TraceError):
            tr.validate()


# ---------------------------------------------------------------------------
# engine integration (shared tiny model; compiles dominate the cost)
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


def test_virtual_clock_span_durations_are_exact(tiny_lm):
    """Satellite check for the deterministic-trace contract: under
    ``FaultPlan(slow_step_s=1.0)`` every stamp lands on the virtual step
    clock, so span durations are EXACT integers — queued ends at the
    admitting step, prefill is zero-width (admit and first token happen in
    one dispatch), decode spans len(tokens)-2 steps (the prefill step
    emits the first token; the final step observes the last)."""
    cfg, model, params = tiny_lm
    eng = Engine(model, params, EngineConfig(num_slots=1, max_len=32))
    eng.warmup(_requests(cfg, [6], [2]))
    eng.set_faults(FaultPlan(slow_step_s=1.0))
    t0 = eng._now()
    for r in _requests(cfg, [6, 6], [4, 3]):
        eng.submit(r)
    out = {r.rid: r for r in eng.run()}
    ev0 = [(n, t - t0) for n, t in out[0].trace.events]
    ev1 = [(n, t - t0) for n, t in out[1].trace.events]
    assert ev0 == [("submit", 0.0), ("admitted", 1.0),
                   ("first_token", 1.0), ("end:ok", 3.0)]
    # the slot frees at step 3; the queued request admits the next step
    assert ev1 == [("submit", 0.0), ("admitted", 4.0),
                   ("first_token", 4.0), ("end:ok", 5.0)]
    for res, gen in ((out[0], 4), (out[1], 3)):
        assert res.trace.validate()
        spans = {s.name: s.duration for s in res.trace.spans()}
        assert spans["prefill"] == 0.0
        assert spans["decode"] == float(gen - 2)
        # result-level timings are the trace's, to the same clock
        assert res.latency == res.trace.events[-1][1] - res.trace.events[0][1]
        assert res.ttft == spans["queued"] + spans["prefill"]
        assert res.queue_time == spans["queued"]
    # histograms saw the same exact values
    snap = eng.metrics_snapshot()
    ttft = snapshot_series(snap, "histograms", "request_ttft_seconds")
    assert ttft["count"] == 2
    assert ttft["sum"] == pytest.approx(1.0 + 4.0)


def test_engine_traces_cover_every_terminal_status(tiny_lm):
    cfg, model, params = tiny_lm
    eng = Engine(model, params, EngineConfig(num_slots=1, max_len=32,
                                             max_queue=2))
    eng.warmup(_requests(cfg, [6], [4]))
    ok, queued, shed = _requests(cfg, [6, 6, 6], [4, 8, 4])
    eng.submit(ok)
    eng.submit(queued)
    assert eng.try_submit(shed) is False            # queue full -> rejected
    eng.step()
    assert eng.cancel(1)                            # queued request
    out = {r.rid: r for r in eng.run()}
    assert out[0].status == "ok"
    assert out[1].status == "cancelled"
    assert out[2].status == "rejected"
    for res in out.values():
        assert res.trace is not None and res.trace.validate()
        assert res.trace.status == res.status
    assert res_names(out[2]) == ["submit", "end:rejected"]
    assert "admitted" not in res_names(out[1])
    # per-status registry counts match the results
    snap = eng.metrics_snapshot()
    for status in ("ok", "cancelled", "rejected"):
        s = snapshot_series(snap, "counters", "engine_requests_total",
                            {"status": status})
        assert s["value"] == 1, status


def res_names(res):
    return [n for n, _ in res.trace.events]


def test_engine_trace_deadline_and_error(tiny_lm):
    cfg, model, params = tiny_lm
    eng = Engine(model, params, EngineConfig(num_slots=1, max_len=32))
    eng.warmup(_requests(cfg, [6], [4]))
    eng.set_faults(FaultPlan(slow_step_s=1.0))      # deterministic clock
    late = _requests(cfg, [6], [20], base=60, deadline_s=5.0)[0]
    eng.submit(late)
    out = {r.rid: r for r in eng.run()}
    # fresh scripted plan: poison rid 61's logits on its third step
    eng.set_faults(FaultPlan(slow_step_s=1.0,
                             script=((3, "nan_logits", 61),)))
    poisoned = _requests(cfg, [6], [20], base=61, seed=2)[0]
    eng.submit(poisoned)
    out.update({r.rid: r for r in eng.run()})
    assert out[60].status == "deadline"
    assert out[61].status == "error"
    for res in out.values():
        assert res.trace.validate()
        assert res.trace.status == res.status
    # the deadline fired mid-decode: the trace got a first token first
    assert "first_token" in res_names(out[60])


def test_engine_trace_preempt_resume_spans(tiny_lm):
    cfg, model, params = tiny_lm
    eng = Engine(model, params, EngineConfig(
        num_slots=3, max_len=48, page_size=8,
        num_pages=9, prefix_caching=False))
    # overload-shaped trace: 3 slots x 5 pages each vs a 9-page pool, so
    # decode extension must preempt (mirrors the bench overload scenario)
    reqs = _requests(cfg, [28, 29, 30, 31, 28, 29],
                     [12, 12, 12, 12, 12, 12], seed=1)
    eng.warmup(reqs[:2])
    for r in reqs:
        eng.submit(r)
    out = {r.rid: r for r in eng.run()}
    assert eng.preemptions > 0 and eng.resumes > 0
    preempted = [r for r in out.values()
                 if "preempt" in res_names(r)]
    assert preempted, "overloaded pool must preempt at least one request"
    for res in out.values():
        assert res.status == "ok"
        assert res.trace.validate()
    resumed = [r for r in preempted if "resume" in res_names(r)]
    assert resumed
    spans = resumed[0].trace.spans()
    pre = [s for s in spans if s.name == "preempted"]
    assert pre and all(s.duration > 0 for s in pre)
    # preempted spans nest inside the decode span
    decode = next(s for s in spans if s.name == "decode")
    for s in pre:
        assert decode.start <= s.start and s.end <= decode.end


def test_queue_trace_ring_reports_dropped(tiny_lm):
    cfg, model, params = tiny_lm
    eng = Engine(model, params, EngineConfig(num_slots=1, max_len=32,
                                             queue_trace_samples=4))
    eng.warmup(_requests(cfg, [6], [4]))
    for r in _requests(cfg, [6, 6, 6], [6, 6, 6]):
        eng.submit(r)
    eng.run()
    qs = eng.queue_stats()
    assert len(qs["trace"]) == 4                    # ring capacity
    assert qs["dropped"] > 0                        # early samples displaced
    assert qs["samples"] == len(qs["trace"]) + qs["dropped"]
    assert qs["peak"] >= 2


def test_metrics_off_engine_behavior_identical(tiny_lm, assert_flat_compiles):
    """The overhead contract: a disabled registry must not change engine
    behavior — greedy tokens bit-identical, post-warmup compile counts
    flat and equal, and every dispatch counter identical (same number of
    prefill/chunk/decode programs ran). Also covers 'metrics on adds no
    dispatches': both runs execute the same device work."""
    cfg, model, params = tiny_lm
    reqs = _requests(cfg, [6, 9, 7, 11], [6, 4, 8, 5], seed=3)

    def drive(registry):
        eng = Engine(model, params,
                     EngineConfig(num_slots=2, max_len=32),
                     registry=registry)
        warm = eng.warmup(reqs)
        with assert_flat_compiles(eng, warm):        # post-warmup recompile
            for r in reqs:
                eng.submit(r)
            out = {r.rid: r.tokens for r in eng.run()}
        compiled = eng.compile_counts()
        dispatch = {
            "prefill_dispatches": eng.prefill_dispatches,
            "chunk_dispatches": eng.chunk_dispatches,
            "decode_steps": eng.decode_steps,
            "active_slot_steps": eng.active_slot_steps,
        }
        return out, compiled, dispatch, eng

    out_on, compiled_on, dispatch_on, eng_on = drive(None)
    out_off, compiled_off, dispatch_off, eng_off = drive(
        MetricsRegistry(enabled=False))
    assert out_on == out_off                        # bit-identical greedy
    assert compiled_on == compiled_off
    assert dispatch_on == dispatch_off
    # disabled registry: no traces, no histogram samples — but the
    # counters (the engine's own bookkeeping) still count
    res_off = eng_off._done if eng_off._done else []
    snap_off = eng_off.metrics_snapshot()
    ttft = snapshot_series(snap_off, "histograms", "request_ttft_seconds")
    assert ttft is None or ttft["count"] == 0
    ok = snapshot_series(snap_off, "counters", "engine_requests_total",
                         {"status": "ok"})
    assert ok["value"] == len(reqs)
    snap_on = eng_on.metrics_snapshot()
    assert snapshot_series(snap_on, "histograms",
                           "request_ttft_seconds")["count"] == len(reqs)


def test_engine_snapshot_exposition_round_trip(tiny_lm):
    cfg, model, params = tiny_lm
    eng = Engine(model, params, EngineConfig(num_slots=2, max_len=32))
    eng.warmup(_requests(cfg, [6], [4]))
    for r in _requests(cfg, [6, 8], [4, 4], seed=5):
        eng.submit(r)
    eng.run()
    snap = eng.metrics_snapshot()
    parsed = parse_exposition(eng.metrics.to_prometheus())
    for fam in ("engine_decode_steps_total", "engine_requests_total",
                "request_ttft_seconds_bucket", "engine_queue_depth",
                "engine_slots_active"):
        assert fam in parsed, fam
    dec = snapshot_series(snap, "counters", "engine_decode_steps_total")
    assert dec["value"] == eng.decode_steps > 0
    (key, val), = [kv for kv in parsed["engine_decode_steps_total"].items()]
    assert val == eng.decode_steps
