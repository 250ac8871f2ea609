"""Continuous-batching engine load test: dense-KV vs INT8-KV page pool,
plus burst-arrival and long-prompt scenarios.

Generates a Zipf-length request trace (many short prompts/outputs, a heavy
tail — the open-ended-serving regime), drives the engine at equal slot
counts with the dense (bf16) and the INT8 per-head-group quantized KV
cache, and reports throughput, p50/p99 request latency, time-to-first-token,
slot utilization, resident cache bytes, and compiled-program counts (flat
across the post-warmup trace ⇔ no recompilation). Two targeted scenarios
ride along:

- **burst** — a clump of same-bucket arrivals: batched admission must
  cover the burst in far fewer prefill dispatches than requests (a slots-
  wide burst costs ONE device call), with no post-warmup compiles;
- **long_prompt** — prompts beyond the largest bucket stream through the
  bucket-width chunked-prefill program; greedy output stays bit-identical
  to the static path;
- **shared_prefix** — a Zipf trace behind one shared system prefix:
  repeat prefixes admit copy-free off the prefix cache
  (reports hit rate and prompt tokens reused), parity-checked;
- **overload** — an oversubscribed page pool behind a bounded queue
  (``max_queue``): decode extension preempts the youngest request (pages
  spill to host) and resumes it later, the burst tail sheds with a
  ``rejected`` status, queue depth over time lands in the JSON, and every
  completed request — preempted ones included — stays bit-identical;
- **chaos** — the overload trace under a seeded ``FaultPlan`` (injected
  allocation + spill/restore failures) with a mid-flight cancel:
  ``check_invariants()`` is asserted after every step, every request ends
  terminal, the pool drains to zero, and each ``ok`` survivor's output is
  bit-identical to a fault-free run of the same trace.

Every row carries the pool's ``page_stats``.

    PYTHONPATH=src python -m benchmarks.engine_bench [--tiny]

Emits ``results/BENCH_engine.json`` via the shared emitter (CI uploads it
next to the other BENCH artifacts). Its times are CPU/interpreter
measurements of the control flow, not speed claims. A greedy parity check
against the static serving path runs on the first few requests of the
dense trace — the engine must be bit-identical per request. The engine
decodes through the fused flash-decode kernel by default, so that slice
doubles as the fused-vs-reference gate; the INT8 row additionally reruns
its trace through the reference gather-then-attend path (bit-identical
greedy tokens) and bounds the fused decode-logit gap at the 0.05·scale
tolerance test_engine.py uses. Every throughput row carries per-status token
accounting (``tokens_by_status``, ``ok_tok_per_s``) so scenarios that
shed or fault stay comparable to their fault-free baselines.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit_json
from repro.configs import get_tiny_config
from repro.launch.serve import (build_trace, make_step_fns,
                                static_greedy_reference)
from repro.models import build_model
from repro.obs import hist_quantile, snapshot_series
from repro.serving import Engine, EngineConfig


def _throughput(results, wall):
    """Per-status token accounting for a driven trace.

    ``wall`` spans the whole drive — including queue residency of requests
    that end rejected/errored with zero or partial tokens — so the
    all-results ``tok_per_s`` understates decode speed on any trace that
    sheds or faults. ``ok_tok_per_s`` divides only completed requests'
    tokens by the same wall, which is what makes the chaos/overload rows
    comparable to their fault-free baselines; ``tokens_by_status`` keeps
    the gap auditable (partial tokens from cancelled/errored requests are
    visible instead of silently folded into one number)."""
    tok_by_status = {}
    for r in results:
        tok_by_status[r.status] = (tok_by_status.get(r.status, 0)
                                   + len(r.tokens))
    n_tok = sum(tok_by_status.values())
    w = max(wall, 1e-9)
    return {
        "generated_tokens": n_tok,
        "tokens_by_status": tok_by_status,
        "wall_s": wall,
        "tok_per_s": n_tok / w,
        "ok_tok_per_s": tok_by_status.get("ok", 0) / w,
    }


def _registry_stats(engine, results):
    """Registry-derived slice of a result row: per-status request counts
    off ``engine_requests_total`` and TTFT percentiles off the
    ``request_ttft_seconds`` histogram (bucket-interpolated, clamped to
    the observed min/max). The per-status counts are cross-checked
    against the results list, so every bench run doubles as a gate that
    the telemetry agrees with ground truth."""
    snap = engine.metrics_snapshot()
    statuses = {}
    fam = snap["counters"].get("engine_requests_total", {"series": ()})
    for s in fam["series"]:
        if s["value"]:
            statuses[s["labels"]["status"]] = int(s["value"])
    tally = {}
    for r in results:
        tally[r.status] = tally.get(r.status, 0) + 1
    assert statuses == tally, \
        f"registry status counts {statuses} != result statuses {tally}"
    ttft = snapshot_series(snap, "histograms", "request_ttft_seconds")
    have = ttft is not None and ttft["count"] > 0
    return {
        "statuses": statuses,
        "ttft_p50_ms": 1e3 * hist_quantile(ttft, 0.5) if have else 0.0,
        "ttft_p99_ms": 1e3 * hist_quantile(ttft, 0.99) if have else 0.0,
    }


def run_engine(model, params, cfg, ecfg: EngineConfig, reqs):
    """One warmed engine pass over the trace → metrics dict. Submission
    goes through ``try_submit``, so with ``max_queue`` set the shed
    requests land in the results as ``rejected`` (and in ``statuses``)
    instead of raising; latency percentiles cover completed requests.
    Statuses and TTFT percentiles come from the engine's metrics
    registry (``warmup`` resets it, so they span exactly this trace)."""
    engine = Engine(model, params, ecfg)
    compiled_warm = engine.warmup(reqs)

    t0 = time.perf_counter()
    for r in reqs:
        engine.try_submit(r)
    results = engine.run()
    wall = time.perf_counter() - t0

    done = [r for r in results if r.ok]
    lats = sorted(r.latency for r in done) or [0.0]
    compiled = dict(engine.compile_counts())
    qs = engine.queue_stats()
    return {
        "requests": len(results),
        **_registry_stats(engine, results),
        **_throughput(results, wall),
        "latency_p50_ms": 1e3 * lats[len(lats) // 2],
        "latency_p99_ms": 1e3 * lats[min(len(lats) - 1,
                                         int(len(lats) * 0.99))],
        "slot_utilization": engine.utilization(),
        "kv_cache_bytes": engine.kv_cache_bytes(),
        "prefill_dispatches": engine.prefill_dispatches,
        "prefill_admitted": engine.prefill_admitted,
        "chunk_dispatches": engine.chunk_dispatches,
        "chunked_admitted": engine.chunked_admitted,
        "queue_depth_peak": qs["peak"],
        "queue_depth_mean": qs["mean"],
        "rejected": qs["rejected"],
        "compiled_programs": compiled,
        "recompiled_after_warmup": compiled != compiled_warm,
        "page_stats": engine.page_stats(),
    }, results


def check_parity(model, params, reqs, results, max_len, n_check: int,
                 step_fns=None):
    """Greedy engine outputs vs the static path, bit-identical per request.
    ``step_fns`` is hoisted by the caller so the static decode program
    compiles once, not per checked request."""
    by_rid = {r.rid: r.tokens for r in results}
    for req in reqs[:n_check]:
        ref = static_greedy_reference(model, params, req, max_len, step_fns)
        assert by_rid[req.rid] == ref, \
            f"engine/static divergence rid={req.rid}: {by_rid[req.rid]} != {ref}"
    return n_check


def check_fused_reference_tokens(model, params, cfg, ecfg, reqs, results):
    """Rerun the identical trace through the reference dequant-then-attend
    path (``use_fused_decode=False``) and require greedy outputs
    bit-identical per request. Applied to the INT8 rows, where the static
    oracle doesn't cover the quantized storage."""
    ref_cfg = dataclasses.replace(ecfg, use_fused_decode=False)
    _, ref_results = run_engine(model, params, cfg, ref_cfg, reqs)
    ref = {r.rid: r.tokens for r in ref_results}
    got = {r.rid: r.tokens for r in results}
    assert got == ref, "fused INT8 decode diverged from the reference path"
    return len(ref)


def check_int8_fused_logits(model, params, cfg, ecfg, reqs):
    """One decode step over an INT8 page pool that served ``reqs``, fused
    vs reference read: logits must agree within the 0.05·scale bound
    test_engine.py enforces for quantized storage. Each slot reads a run
    of pages at a position spread over [0, max_len). The measured gap is
    ~1e-6 — the kernel's in-tile dequant reproduces the reference
    expansion's op order — and lands in the JSON so regressions are
    visible, not just pass/fail."""
    engine = Engine(model, params, ecfg)
    for r in reqs:
        engine.submit(r)
    engine.run()
    s, n = ecfg.num_slots, engine.pages_per_slot
    cache = {"k": engine.kv["k"], "v": engine.kv["v"],
             "pos": jnp.linspace(0, ecfg.max_len - 1, s).astype(jnp.int32),
             "table": jnp.arange(s * n, dtype=jnp.int32).reshape(s, n)
             % engine.alloc.num_pages}
    tok = jnp.arange(1, s + 1, dtype=jnp.int32)[:, None]
    fused_m = dataclasses.replace(model, use_fused_decode=True)
    d_ref, _ = jax.jit(model.decode_step)(params, tok, cache)
    d_fused, _ = jax.jit(fused_m.decode_step)(params, tok, cache)
    scale = float(jnp.abs(d_ref).max())
    gap = float(jnp.abs(d_fused - d_ref).max())
    assert gap < 0.05 * scale, (gap, scale)
    return gap, scale


def burst_scenario(model, params, cfg, *, slots, burst, plen, gen, seed=1):
    """A clump of same-bucket arrivals (the bursty regime): batched
    admission must cover the burst in ceil-ish(burst/slots) prefill
    dispatches, not one per request."""
    from repro.serving import GenerationRequest, SamplingParams
    rng = np.random.default_rng(seed)
    reqs = [GenerationRequest(
                rid=i,
                prompt=rng.integers(1, cfg.vocab_size,
                                    size=plen).astype(np.int32),
                max_new_tokens=gen, sampling=SamplingParams())
            for i in range(burst)]
    ecfg = EngineConfig(num_slots=slots, max_len=plen + gen,
                        kv_dtype=jnp.float32)
    row, results = run_engine(model, params, cfg, ecfg, reqs)
    row.update(burst=burst, prompt_len=plen,
               admitted_per_dispatch=row["prefill_admitted"]
               / max(row["prefill_dispatches"], 1))
    assert row["prefill_dispatches"] < burst, \
        "burst admission must batch (fewer dispatches than requests)"
    assert row["recompiled_after_warmup"] is not True
    n = check_parity(model, params, reqs, results, plen + gen,
                     min(4, burst), step_fns=make_step_fns(model))
    row["parity_checked"] = n
    return row


def long_prompt_scenario(model, params, cfg, *, slots, buckets, max_len,
                         gen, seed=2):
    """Prompts beyond the largest bucket: chunked prefill streams them
    through the bucket-width program — greedy output stays bit-identical
    to the static path, with no max_len-wide compile."""
    from repro.serving import GenerationRequest, SamplingParams
    rng = np.random.default_rng(seed)
    wmax = buckets[-1]
    lens = [int(l) for l in
            rng.integers(wmax + 1, max_len - gen, size=2 * slots)]
    lens[0] = max_len - gen                        # the max_len-scale tail
    reqs = [GenerationRequest(
                rid=i,
                prompt=rng.integers(1, cfg.vocab_size,
                                    size=l).astype(np.int32),
                max_new_tokens=gen, sampling=SamplingParams())
            for i, l in enumerate(lens)]
    ecfg = EngineConfig(num_slots=slots, max_len=max_len,
                        prompt_buckets=buckets, kv_dtype=jnp.float32)
    row, results = run_engine(model, params, cfg, ecfg, reqs)
    row.update(prompt_buckets=list(buckets), max_prompt_len=max(lens),
               mean_prompt_len=float(np.mean(lens)))
    assert row["chunked_admitted"] == len(reqs)
    assert row["recompiled_after_warmup"] is not True
    n = check_parity(model, params, reqs, results, max_len, 3,
                     step_fns=make_step_fns(model))
    row["parity_checked"] = n
    return row


def shared_prefix_scenario(model, params, cfg, *, slots, requests, seed=3):
    """Zipf-tail trace behind one shared system prefix (the production
    shape prefix caching exists for): the paged engine admits repeat
    prefixes copy-free — reused prompt tokens never re-prefill — while
    greedy output stays bit-identical to the static path."""
    from repro.serving import GenerationRequest, SamplingParams
    pg, prefix_len, max_len, gen = 8, 16, 48, 6
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, cfg.vocab_size, size=prefix_len).astype(np.int32)
    reqs = []
    for i in range(requests):
        tail = rng.integers(1, cfg.vocab_size,
                            size=int(np.clip(rng.zipf(1.6), 1, 16)))
        reqs.append(GenerationRequest(
            rid=i, prompt=np.concatenate([prefix, tail.astype(np.int32)]),
            max_new_tokens=gen, sampling=SamplingParams()))
    ecfg = EngineConfig(num_slots=slots, max_len=max_len,
                        kv_dtype=jnp.float32, page_size=pg)
    row, results = run_engine(model, params, cfg, ecfg, reqs)
    ps = row["page_stats"]
    hits, misses = ps["prefix_hits"], ps["prefix_misses"]
    row.update(shared_prefix_len=prefix_len, page_size=pg,
               prefix_hit_rate=hits / max(hits + misses, 1),
               prompt_tokens=sum(r.prompt_len for r in reqs),
               prompt_tokens_reused=ps["prefix_hit_tokens"])
    assert hits > 0, "shared-prefix trace must hit the prefix cache"
    assert ps["prefix_hit_tokens"] > 0
    assert row["recompiled_after_warmup"] is not True
    n = check_parity(model, params, reqs, results, max_len,
                     min(4, requests), step_fns=make_step_fns(model))
    row["parity_checked"] = n
    return row


def _overload_requests(cfg, requests, gen, seed):
    from repro.serving import GenerationRequest, SamplingParams
    rng = np.random.default_rng(seed)
    return [GenerationRequest(
                rid=i,
                prompt=rng.integers(1, cfg.vocab_size,
                                    size=int(28 + i % 4)).astype(np.int32),
                max_new_tokens=gen, sampling=SamplingParams())
            for i in range(requests)]


def overload_scenario(model, params, cfg, *, requests=8, max_queue=6,
                      seed=4):
    """Page-pool oversubscription (num_pages well below slots' worst case)
    PLUS a bounded queue: decode extension must preempt the youngest
    request, spill its pages to host, and resume it later — while the
    tail of the burst sheds at ``max_queue`` with a ``rejected`` status.
    Greedy output stays bit-identical to the static path for every
    completed request, preempted ones included; queue depth over time
    rides along in the row."""
    pg, max_len, gen, slots, num_pages = 8, 48, 12, 3, 9
    reqs = _overload_requests(cfg, requests, gen, seed)
    ecfg = EngineConfig(num_slots=slots, max_len=max_len,
                        kv_dtype=jnp.float32,
                        page_size=pg, num_pages=num_pages,
                        prefix_caching=False, max_queue=max_queue)
    engine = Engine(model, params, ecfg)
    engine.warmup(reqs)
    t0 = time.perf_counter()
    shed = [r.rid for r in reqs if not engine.try_submit(r)]
    results = engine.run()
    wall = time.perf_counter() - t0
    row, _ = _result_row(engine, results, wall)
    ps = row["page_stats"]
    row.update(num_pages=num_pages, page_size=pg, max_queue=max_queue,
               pool_utilization=ps["peak_pages_in_use"] / num_pages,
               queue_depth_trace=engine.queue_stats()["trace"])
    assert ps["preemptions"] > 0 and ps["resumes"] > 0, \
        "oversubscribed pool must preempt"
    assert ps["peak_pages_in_use"] <= num_pages
    assert len(shed) == max(0, requests - max_queue), \
        "every submit past max_queue must shed"
    assert row["queue_depth_peak"] <= max_queue
    # every completed request — preempted-and-resumed ones included —
    # stays exact; the shed tail never ran
    survivors = [r for r in reqs if r.rid not in shed]
    n = check_parity(model, params, survivors, results, max_len,
                     len(survivors), step_fns=make_step_fns(model))
    row["parity_checked"] = n
    return row


def _result_row(engine, results, wall):
    """Shared row shape for the stepwise-driven scenarios (overload/chaos);
    mirrors run_engine's metrics without re-submitting. Queue depth over
    time comes off the registry gauge's ring-buffer trace — ``dropped``
    says how many early samples the ring displaced (0 for these short
    drives)."""
    done = [r for r in results if r.ok]
    lats = sorted(r.latency for r in done) or [0.0]
    qs = engine.queue_stats()
    return {
        "requests": len(results),
        **_registry_stats(engine, results),
        **_throughput(results, wall),
        "latency_p50_ms": 1e3 * lats[len(lats) // 2],
        "slot_utilization": engine.utilization(),
        "queue_depth_peak": qs["peak"],
        "queue_depth_mean": qs["mean"],
        "queue_depth_dropped": qs["dropped"],
        "rejected": qs["rejected"],
        "page_stats": engine.page_stats(),
    }, results


def chaos_scenario(model, params, cfg, *, requests=8, seed=5):
    """Overload + injected faults (the acceptance scenario from the
    lifecycle-hardening work): a seeded FaultPlan fires allocation
    failures and spill/restore failures into the oversubscribed paged
    pool while the queue sheds at ``max_queue`` and one request is
    cancelled mid-flight. The engine must stay failure-atomic —
    ``check_invariants()`` holds after EVERY step, every request reaches
    a terminal status, the pool drains to zero — and every ``ok``
    survivor's output is bit-identical to a fault-free run of the same
    trace."""
    from repro.serving import FaultPlan
    pg, max_len, gen, slots, num_pages = 8, 48, 12, 3, 9
    max_queue = 6
    ecfg = EngineConfig(num_slots=slots, max_len=max_len,
                        kv_dtype=jnp.float32,
                        page_size=pg, num_pages=num_pages,
                        prefix_caching=False, max_queue=max_queue)

    def drive(faults, cancel_after=-1):
        engine = Engine(model, params, ecfg)
        reqs = _overload_requests(cfg, requests, gen, seed)
        engine.warmup(reqs)
        if faults is not None:
            engine.set_faults(faults)
        t0 = time.perf_counter()
        shed = [r.rid for r in reqs if not engine.try_submit(r)]
        cancelled, steps = -1, 0
        while not engine.scheduler.idle:
            engine.step()
            steps += 1
            engine.check_invariants()           # after EVERY step
            if cancelled < 0 and 0 <= cancel_after <= engine.decode_steps:
                live = engine.scheduler.active_slots()
                if live:
                    cancelled = engine.scheduler.slots[live[-1]].request.rid
                    assert engine.cancel(cancelled)
                    engine.check_invariants()
            assert steps < 5000, "chaos drive runaway"
        wall = time.perf_counter() - t0
        results, engine._done = list(engine._done), []
        assert engine.alloc.pages_in_use == 0, "chaos leaked pages"
        return engine, reqs, shed, cancelled, results, wall

    _, base_reqs, base_shed, _, base_results, _ = drive(None)
    baseline = {r.rid: r.tokens for r in base_results if r.ok}

    plan = FaultPlan(seed=11, alloc_fail=0.15, spill_fail=0.3)
    engine, reqs, shed, cancelled, results, wall = drive(plan,
                                                         cancel_after=3)
    row, _ = _result_row(engine, results, wall)
    row.update(fault_plan={"seed": plan.seed, "alloc_fail": plan.alloc_fail,
                           "spill_fail": plan.spill_fail},
               faults_fired=dict(plan.fired), max_queue=max_queue,
               cancelled_rid=cancelled,
               queue_depth_trace=engine.queue_stats()["trace"])
    assert shed == base_shed                     # shedding is deterministic
    assert {r.rid for r in results} == {r.rid for r in reqs}, \
        "every request must reach a terminal status"
    survivors = 0
    for r in results:
        if r.ok:
            assert r.tokens == baseline[r.rid], \
                f"chaos survivor rid={r.rid} diverged from fault-free run"
            survivors += 1
    row["parity_checked"] = survivors
    assert survivors > 0
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama32-1b")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--max-prompt", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--parity-check", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CI sizes: 4 slots, 16 requests, short lengths")
    args = ap.parse_args()
    if args.tiny:
        args.slots, args.requests = 4, 16
        args.max_prompt, args.max_new, args.parity_check = 24, 12, 4

    cfg = get_tiny_config(args.arch)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    max_len = args.max_prompt + args.max_new
    reqs = build_trace(cfg, num_requests=args.requests,
                       max_prompt=args.max_prompt, max_new=args.max_new,
                       seed=args.seed)
    mean_p = float(np.mean([r.prompt_len for r in reqs]))
    mean_n = float(np.mean([r.max_new_tokens for r in reqs]))
    print(f"engine bench: {args.arch} tiny, slots={args.slots} "
          f"requests={args.requests} max_len={max_len} "
          f"(mean prompt {mean_p:.1f}, mean new {mean_n:.1f})")

    # page_size must divide max_len for bit-parity with the static path;
    # 12 divides both the CI (24+12) and default (48+24) shapes
    page = 12 if max_len % 12 == 0 else 8
    rows = {}
    for name, quant in (("dense", False), ("int8", True)):
        ecfg = EngineConfig(num_slots=args.slots, max_len=max_len,
                            kv_dtype=jnp.bfloat16, kv_quantized=quant,
                            page_size=page)
        rows[name], results = run_engine(model, params, cfg, ecfg, reqs)
        if name == "dense" and args.parity_check:
            # bf16 cache rounds K/V — rerun the parity slice on an f32
            # cache. The engine decodes FUSED (use_fused_decode defaults
            # on) while static_greedy_reference runs the unfused reference
            # model, so this is the fused-vs-reference greedy gate.
            ecfg32 = EngineConfig(num_slots=args.slots, max_len=max_len,
                                  kv_dtype=jnp.float32, page_size=page)
            _, res32 = run_engine(model, params, cfg, ecfg32, reqs)
            n = check_parity(model, params, reqs, res32, max_len,
                             args.parity_check,
                             step_fns=make_step_fns(model))
            print(f"  parity: {n}/{n} fused-engine requests bit-identical "
                  f"to the reference static path (f32 KV)")
        if quant:
            n = check_fused_reference_tokens(model, params, cfg, ecfg,
                                             reqs, results)
            rows[name]["fused_parity_checked"] = n
            print(f"  parity: {name} fused == reference path for "
                  f"{n}/{n} requests (greedy tokens)")
            gap, lscale = check_int8_fused_logits(model, params, cfg, ecfg,
                                                  reqs)
            rows[name]["fused_logit_gap"] = gap
            rows[name]["fused_logit_bound"] = 0.05 * lscale
            print(f"  parity: int8 fused decode logits within {gap:.2e} of "
                  f"the reference read (bound {0.05 * lscale:.2e})")
        r = rows[name]
        print(f"  {name:11s} {r['tok_per_s']:8.0f} tok/s   "
              f"p50 {r['latency_p50_ms']:7.1f}ms   "
              f"p99 {r['latency_p99_ms']:7.1f}ms   "
              f"util {r['slot_utilization']:.2f}   "
              f"kv {r['kv_cache_bytes'] / 1e6:6.2f}MB   "
              f"recompiled={r['recompiled_after_warmup']}")

    ratio = rows["dense"]["kv_cache_bytes"] / max(rows["int8"]["kv_cache_bytes"], 1)
    assert rows["int8"]["kv_cache_bytes"] < rows["dense"]["kv_cache_bytes"], \
        "INT8 cache must be smaller than dense"
    assert rows["dense"]["recompiled_after_warmup"] is not True
    assert rows["int8"]["recompiled_after_warmup"] is not True
    print(f"  int8 kv cache = {1 / ratio:.2f}x dense bytes "
          f"({ratio:.2f}x smaller)")

    burst = burst_scenario(model, params, cfg, slots=args.slots,
                           burst=2 * args.slots,
                           plen=args.max_prompt - args.max_prompt // 4,
                           gen=max(2, args.max_new // 3))
    print(f"  burst {burst['burst']} same-bucket requests -> "
          f"{burst['prefill_dispatches']} prefill dispatches "
          f"({burst['admitted_per_dispatch']:.1f} admitted/dispatch), "
          f"{burst['tok_per_s']:.0f} tok/s, parity {burst['parity_checked']} "
          f"reqs, recompiled={burst['recompiled_after_warmup']}")

    shared = shared_prefix_scenario(model, params, cfg, slots=args.slots,
                                    requests=3 * args.slots)
    sps = shared["page_stats"]
    print(f"  shared-prefix ({shared['shared_prefix_len']} tokens x "
          f"{shared['requests']} requests): "
          f"hit rate {shared['prefix_hit_rate']:.0%}, "
          f"{shared['prompt_tokens_reused']}/{shared['prompt_tokens']} prompt "
          f"tokens reused, {sps['prefix_cached_pages']} pages cached, "
          f"parity {shared['parity_checked']} reqs, "
          f"recompiled={shared['recompiled_after_warmup']}")

    overload = overload_scenario(model, params, cfg)
    ops = overload["page_stats"]
    print(f"  overload ({overload['num_pages']} pages, peak "
          f"{ops['peak_pages_in_use']}): {ops['preemptions']} preemptions, "
          f"{ops['resumes']} resumes, {ops['pages_spilled']} pages spilled, "
          f"pool util {overload['pool_utilization']:.2f}, "
          f"queue peak {overload['queue_depth_peak']} "
          f"(max_queue {overload['max_queue']}, "
          f"{overload['rejected']} shed), "
          f"ttft p50 {overload['ttft_p50_ms']:.1f}ms "
          f"p99 {overload['ttft_p99_ms']:.1f}ms, "
          f"statuses {overload['statuses']}, "
          f"parity {overload['parity_checked']} reqs")

    chaos = chaos_scenario(model, params, cfg)
    cps = chaos["page_stats"]
    print(f"  chaos (seeded faults {chaos['faults_fired']}): "
          f"statuses {chaos['statuses']}, "
          f"tokens by status {chaos['tokens_by_status']}, "
          f"{chaos['ok_tok_per_s']:.0f} completed-tok/s "
          f"(vs {chaos['tok_per_s']:.0f} all-tok/s), "
          f"ttft p50 {chaos['ttft_p50_ms']:.1f}ms "
          f"p99 {chaos['ttft_p99_ms']:.1f}ms, "
          f"{cps['preemptions']} preemptions, "
          f"{chaos['rejected']} shed, cancel rid={chaos['cancelled_rid']}, "
          f"invariants held every step, "
          f"parity {chaos['parity_checked']} survivors")

    lp_buckets = (8, args.max_prompt // 2)
    longp = long_prompt_scenario(model, params, cfg, slots=args.slots,
                                 buckets=lp_buckets, max_len=max_len,
                                 gen=max(2, args.max_new // 3))
    print(f"  long-prompt (buckets {lp_buckets}, prompts up to "
          f"{longp['max_prompt_len']}): {longp['chunked_admitted']} chunked "
          f"via {longp['chunk_dispatches']} chunk dispatches, "
          f"{longp['tok_per_s']:.0f} tok/s, parity {longp['parity_checked']} "
          f"reqs, recompiled={longp['recompiled_after_warmup']}")

    out = emit_json("engine", {
        "arch": args.arch,
        "slots": args.slots, "requests": args.requests,
        "max_len": max_len,
        "mean_prompt_len": mean_p, "mean_new_tokens": mean_n,
        "dense": rows["dense"], "int8": rows["int8"],
        "burst": burst, "long_prompt": longp,
        "shared_prefix": shared, "overload": overload, "chaos": chaos,
        "kv_compression_x": ratio,
    })
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
