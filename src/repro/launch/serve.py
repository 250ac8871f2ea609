"""Serving launcher: static batch or continuous-batching engine, with
optional compressed weights (what the paper compresses models FOR).

Static path (one prefill + fixed-length greedy decode, uniform batch):

  PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --tiny \
      --batch 8 --prompt-len 32 --gen 32 [--ckpt results/compressed_ckpt]

Engine path (continuous batching over a mixed-length trace, batched
same-bucket admissions, chunked prefill for prompts beyond the largest
bucket, per-request sampling, optional INT8 KV cache — see
docs/serving.md):

  PYTHONPATH=src python -m repro.launch.serve --tiny --engine continuous \
      --requests 32 --slots 8 --gen 32 [--buckets 8,16] [--kv-quant] \
      [--page-size 16 --pages 0] [--mixed-admission] [--verify]

The engine's KV cache is a fixed-size-page pool with a free-list
allocator, refcounted shared-prefix page reuse, and preemption-aware
scheduling (youngest spills to host under page pressure, resumes
bit-identically). Greedy outputs stay bit-identical to the static path
whenever page-size divides max-len.

With ``--packed`` the checkpoint is a packed QTensor checkpoint (written by
``repro.launch.compress --save-packed``): quantized layers stay packed
``QTensor`` leaves of the param tree end-to-end — the jitted forward pass
reads the integer codes through the fused dequant-matmul, no dense floats
are ever materialized for them (``--materialize`` restores the legacy
dense expansion). Token selection runs inside the jitted steps on both
paths, so decode transfers one int32 per request per step, not the logits.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_tiny_config
from repro.data import DataConfig, ZipfMarkov
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.quant import QTensor
from repro.serving.engine import step_jit


def qtensor_leaves(params) -> list:
    """The QTensor leaves of a params tree. ``is_leaf`` stops traversal AT
    each QTensor so stacked leaves are counted once (their children carry
    the block/expert dims)."""
    return [l for l in jax.tree.leaves(
                params, is_leaf=lambda x: isinstance(x, QTensor))
            if isinstance(l, QTensor)]


def dense_itemsize(params) -> int:
    """Bytes per element of the tree's dense serving dtype: the first
    floating dense leaf decides (bf16 trees → 2, f32 → 4; QTensor children
    are skipped — their f32 scales are not the serving dtype). 4 when the
    tree has no dense float leaf."""
    for leaf in jax.tree.leaves(params,
                                is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(leaf, QTensor):
            continue
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            return jnp.dtype(leaf.dtype).itemsize
    return 4


def packed_weight_bytes(params) -> tuple:
    """(packed_bytes, dense_equiv_bytes) over the QTensor leaves of params.
    The dense equivalent is counted at the tree's ACTUAL dense dtype (a
    bf16 serving tree compares against 2-byte floats, not a hardcoded 4)."""
    itemsize = dense_itemsize(params)
    packed = dense = 0
    for leaf in qtensor_leaves(params):
        packed += leaf.nbytes()
        nibble = leaf.bits == 4 and leaf.packed.shape[-1] * 2 == leaf.shape[1]
        dense += leaf.packed.size * (2 if nibble else 1) * itemsize
    return packed, dense


def make_step_fns(model):
    """Jitted (prefill_fn, decode_fn) with greedy token selection folded in:
    each returns ``(tokens (B,1) int32, cache)`` — full logits never leave
    the device during decode."""
    def prefill_fn(params, batch, cache):
        logits, cache = model.prefill(params, batch, cache)
        return jnp.argmax(logits[:, -1], -1)[:, None], cache

    def decode_fn(params, tok, cache):
        logits, cache = model.decode_step(params, tok, cache)
        return jnp.argmax(logits[:, -1], -1)[:, None], cache

    return step_jit(prefill_fn), step_jit(decode_fn, donate_argnums=2)


def _load_params(args, model):
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt and args.packed:
        params, qts, manifest = CheckpointManager(
            args.ckpt).restore_latest_packed(params,
                                             materialize=args.materialize)
        if params is None:
            raise SystemExit(f"[serve] no checkpoint under {args.ckpt}")
        itemsize = dense_itemsize(params)
        dense = sum(int(np.prod(qt.shape)) * itemsize for qt in qts.values())
        packed_b = sum(qt.nbytes() for qt in qts.values())
        resident, _ = packed_weight_bytes(params)
        print(f"[serve] loaded packed checkpoint step {manifest['step']}: "
              f"{len(qts)} QTensor layers, "
              f"{dense / 1e6:.1f}MB dense ({itemsize}B/elem) -> "
              f"{packed_b / 1e6:.1f}MB packed")
        note = ""
        if args.materialize:
            note = " (materialized dense — legacy path)"
        elif resident == 0:
            note = " (no leaf qualified for packing — serving dense)"
        print(f"[serve] {resident / 1e6:.1f}MB packed weights resident in "
              f"the param tree" + note)
    elif args.ckpt:
        restored, step = CheckpointManager(args.ckpt).restore_latest(
            {"params": params})
        if restored is not None:
            params = restored["params"]
            print(f"[serve] loaded checkpoint step {step}")
    return params


def _serve_static(args, cfg, model, params):
    gen = ZipfMarkov(DataConfig(vocab_size=cfg.vocab_size,
                                seq_len=args.prompt_len,
                                global_batch=args.batch))
    prompts, _ = gen.batch(0)
    max_len = args.prompt_len + args.gen
    cache = model.init_cache(args.batch, max_len, jnp.float32)

    prefill, decode = make_step_fns(model)

    t0 = time.time()
    tok, cache = prefill(params, {"tokens": jnp.asarray(prompts)}, cache)
    jax.block_until_ready(tok)
    t_prefill = time.time() - t0

    out = [tok]
    t1 = time.time()
    for _ in range(args.gen - 1):
        tok, cache = decode(params, tok, cache)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t1

    seqs = np.concatenate([np.asarray(t) for t in out], axis=1)
    print(f"[serve] batch={args.batch} prompt={args.prompt_len} gen={args.gen}")
    print(f"[serve] prefill {args.batch * args.prompt_len / t_prefill:.0f} tok/s, "
          f"decode {args.batch * (args.gen - 1) / max(t_decode, 1e-9):.0f} tok/s")
    print(f"[serve] sample continuation (req 0): {seqs[0][:16].tolist()}")


def build_trace(cfg, *, num_requests: int, max_prompt: int, max_new: int,
                seed: int = 0, temperature: float = 0.0, top_k: int = 0):
    """Mixed-length request trace off the Zipf-Markov corpus: Zipf-ish
    prompt/output lengths (many short, a heavy tail), FIFO submit order."""
    from repro.serving import GenerationRequest, SamplingParams
    gen = ZipfMarkov(DataConfig(vocab_size=cfg.vocab_size, seq_len=max_prompt,
                                global_batch=1, seed=seed))
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(num_requests):
        plen = int(np.clip(rng.zipf(1.6), 1, max_prompt))
        nnew = int(np.clip(rng.zipf(1.4), 1, max_new))
        toks, _ = gen.batch(i)
        reqs.append(GenerationRequest(
            rid=i, prompt=toks[0, :plen].astype(np.int32),
            max_new_tokens=nnew,
            sampling=SamplingParams(temperature=temperature, top_k=top_k,
                                    seed=seed + i)))
    return reqs


def static_greedy_reference(model, params, req, max_len,
                            step_fns=None) -> list:
    """One request on the STATIC path: batch=1, exact prompt, greedy decode,
    same cache length as the engine's max_len — the per-request bit-parity
    oracle shared by ``--verify``, engine_bench, and the engine tests."""
    prefill, decode = step_fns or make_step_fns(model)
    cache = model.init_cache(1, max_len, jnp.float32)
    tok, cache = prefill(params, {"tokens": jnp.asarray(req.prompt[None, :])},
                         cache)
    out = [int(tok[0, 0])]
    for _ in range(req.max_new_tokens - 1):
        tok, cache = decode(params, tok, cache)
        out.append(int(tok[0, 0]))
    return out


def _verify_against_static(model, params, reqs, results, max_len) -> tuple:
    """Greedy engine outputs must be bit-identical to the static path run
    per request (same cache length). Requests that never completed —
    shed at --max-queue, cancelled, errored — have no reference to match
    and are skipped. Returns the mismatch count."""
    step_fns = make_step_fns(model)
    by_rid = {r.rid: r.tokens for r in results if r.ok}
    bad = checked = 0
    for req in reqs:
        if req.rid not in by_rid:
            continue
        checked += 1
        ref = static_greedy_reference(model, params, req, max_len, step_fns)
        if by_rid[req.rid] != ref:
            bad += 1
            print(f"[serve]   MISMATCH rid={req.rid}: {by_rid[req.rid]} != {ref}")
    return bad, checked


def _interval_printer(every: int):
    """Step hook: one-line engine stats every ``every`` steps."""
    t0 = time.time()
    n = 0

    def on_step(eng):
        nonlocal n
        n += 1
        if n % every:
            return
        print(f"[serve] step {n}: active "
              f"{eng.scheduler.num_active}/{eng.cfg.num_slots}, queue "
              f"{len(eng.scheduler.queue)}, decode steps {eng.decode_steps}, "
              f"util {eng.utilization():.2f}, {time.time() - t0:.1f}s")

    return on_step


def _serve_engine(args, cfg, model, params):
    from repro.obs import StepTraceWindow
    from repro.serving import Engine, EngineConfig, RequestStatus

    max_len = min(args.max_len, args.prompt_len + args.gen) \
        if args.max_len else args.prompt_len + args.gen
    if max_len <= args.gen:
        raise SystemExit(f"[serve] --max-len {max_len} leaves no room for "
                         f"prompts at --gen {args.gen}")
    buckets = tuple(int(b) for b in args.buckets.split(",")) \
        if args.buckets else ()
    ecfg = EngineConfig(num_slots=args.slots, max_len=max_len,
                        prompt_buckets=buckets,
                        kv_quantized=args.kv_quant,
                        kv_dtype=jnp.float32,
                        page_size=args.page_size,
                        num_pages=args.pages,
                        prefix_caching=not args.no_prefix_cache,
                        mixed_admission=args.mixed_admission,
                        max_queue=args.max_queue,
                        use_fused_decode=not args.no_fused_decode)
    engine = Engine(model, params, ecfg)
    reqs = build_trace(cfg, num_requests=args.requests,
                       max_prompt=min(args.prompt_len, max_len - args.gen),
                       max_new=args.gen, seed=args.seed,
                       temperature=args.temperature, top_k=args.top_k)
    compiled = engine.warmup(reqs)

    hooks = []
    prof = StepTraceWindow(args.profile_dir, args.profile_steps)
    if prof.enabled:
        print(f"[serve] profiling first {args.profile_steps} steps -> "
              f"{args.profile_dir}")
        prof.start()
        hooks.append(prof.on_step)
    if args.metrics_interval > 0:
        hooks.append(_interval_printer(args.metrics_interval))
    hook = None
    if hooks:
        def hook(eng, _hooks=tuple(hooks)):
            for h in _hooks:
                h(eng)

    t0 = time.time()
    for r in reqs:
        engine.try_submit(r)           # --max-queue sheds, never raises
    results = engine.run(step_hook=hook)
    prof.stop()                        # no-op unless still inside the window
    wall = time.time() - t0
    after = engine.compile_counts()

    done = [r for r in results if r.ok]
    statuses = {}
    for r in results:
        statuses[r.status] = statuses.get(r.status, 0) + 1
    n_tok = sum(len(r.tokens) for r in results)
    lats = sorted(r.latency for r in done) or [0.0]
    p50 = lats[len(lats) // 2]
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
    print(f"[serve] engine: {len(results)} requests, {n_tok} tokens in "
          f"{wall:.2f}s -> {n_tok / wall:.0f} tok/s")
    qs = engine.queue_stats()
    print(f"[serve] statuses {statuses}, queue depth peak {qs['peak']} "
          f"mean {qs['mean']:.1f}"
          + (f", {qs['rejected']} shed at --max-queue {args.max_queue}"
             if args.max_queue else ""))
    print(f"[serve] latency p50 {p50 * 1e3:.1f}ms p99 {p99 * 1e3:.1f}ms, "
          f"slot utilization {engine.utilization():.2f}")
    admit_note = (f"[serve] admissions: {engine.prefill_admitted} requests "
                  f"via {engine.prefill_dispatches} batched prefill "
                  f"dispatches")
    if engine.chunked_admitted:
        admit_note += (f", {engine.chunked_admitted} chunked prompts via "
                       f"{engine.chunk_dispatches} chunk dispatches")
    print(admit_note)
    print(f"[serve] kv cache resident "
          f"{engine.kv_cache_bytes() / 1e6:.2f}MB "
          f"({'int8' if args.kv_quant else 'dense'}, paged), "
          f"compiled programs {after} (warmup {compiled})")
    ps = engine.page_stats()
    print(f"[serve] pages: {ps['pages_in_use']}/{ps['num_pages']} in use "
          f"(peak {ps['peak_pages_in_use']}, size {ps['page_size']}), "
          f"{ps['prefix_cached_pages']} prefix-cached")
    hits, misses = ps["prefix_hits"], ps["prefix_misses"]
    rate = hits / max(hits + misses, 1)
    print(f"[serve] prefix cache: {hits} hits / {misses} misses "
          f"({rate:.0%}), {ps['prefix_hit_tokens']} prompt tokens reused")
    print(f"[serve] preemptions {ps['preemptions']}, resumes "
          f"{ps['resumes']}, pages spilled {ps['pages_spilled']}")
    if after != compiled:
        print("[serve] WARNING: recompilation after warmup")
    if args.verify:
        if args.temperature > 0:
            print("[serve] --verify needs greedy (temperature 0); skipping")
        elif args.kv_quant:
            print("[serve] --verify compares dense-KV greedy; skipping "
                  "under --kv-quant")
        else:
            bad, checked = _verify_against_static(model, params, reqs,
                                                  results, max_len)
            for r in sorted(results, key=lambda r: r.rid):
                print(f"[serve]   rid={r.rid} status={r.status} "
                      f"queue {r.queue_time * 1e3:.1f}ms "
                      f"ttft {r.ttft * 1e3:.1f}ms "
                      f"tpot {r.tpot * 1e3:.2f}ms "
                      f"({len(r.tokens)} tok)")
            print(f"[serve] verify vs static path: "
                  f"{checked - bad}/{checked} completed requests "
                  f"bit-identical ({len(reqs) - checked} not completed)")
            if bad:
                raise SystemExit(1)

    if args.metrics_json:
        engine.metrics_snapshot()      # refresh the state gauges
        engine.metrics.dump_json(args.metrics_json, meta={
            "source": "serve", "engine": "continuous", "arch": args.arch,
            "layout": "paged", "requests": len(reqs), "wall_s": wall})
        prom = args.metrics_json + ".prom"
        with open(prom, "w") as f:
            f.write(engine.metrics.to_prometheus())
        print(f"[serve] metrics snapshot -> {args.metrics_json} (+ {prom})")
    failed = [r for r in results if r.status == RequestStatus.ERROR.value]
    if failed:
        # an error is a step failure the engine isolated to its request
        # (compile or runtime fault on the device path) — never a pass;
        # shed, cancelled and deadline results stay legitimate outcomes
        for r in failed:
            print(f"[serve] ERROR rid={r.rid}: {r.error}")
        raise SystemExit(f"[serve] {len(failed)} request(s) ended in error")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--packed", action="store_true",
                    help="--ckpt is a packed QTensor checkpoint")
    ap.add_argument("--materialize", action="store_true",
                    help="with --packed: expand quantized layers to dense "
                         "floats (legacy path) instead of serving packed")
    ap.add_argument("--engine", choices=("static", "continuous"),
                    default="static",
                    help="static: uniform batch, one prefill + N decodes; "
                         "continuous: continuous batching over a paged "
                         "KV pool")
    ap.add_argument("--slots", type=int, default=8,
                    help="engine: device slots (concurrent requests)")
    ap.add_argument("--requests", type=int, default=32,
                    help="engine: trace length (mixed-length requests)")
    ap.add_argument("--max-len", type=int, default=0,
                    help="engine: longest request, prompt + output "
                         "tokens (0 -> prompt+gen)")
    ap.add_argument("--buckets", default="",
                    help="engine: comma-separated prompt buckets (empty -> "
                         "pow2 buckets covering max-len); prompts beyond "
                         "the largest bucket stream via chunked prefill")
    ap.add_argument("--kv-quant", action="store_true",
                    help="engine: INT8 per-head-group KV cache")
    ap.add_argument("--page-size", type=int, default=16,
                    help="engine: tokens per KV page (greedy bit-parity "
                         "with the static path needs page-size | max-len)")
    ap.add_argument("--pages", type=int, default=0,
                    help="engine: page-pool size (0 -> slots * "
                         "ceil(max-len / page-size), max-len for every "
                         "slot; smaller pools oversubscribe and trigger "
                         "preemption)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="engine: disable shared-prefix page reuse across "
                         "requests")
    ap.add_argument("--mixed-admission", action="store_true",
                    help="engine: admit mixed-bucket FIFO head-runs in one "
                         "right-padded prefill dispatch")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="engine: bound the admission queue — submissions "
                         "past the bound shed with a 'rejected' status "
                         "(0 -> unbounded)")
    ap.add_argument("--no-fused-decode", action="store_true",
                    help="engine: revert decode cache reads to the "
                         "dequant-then-attend reference path instead of "
                         "the fused Pallas flash-decode kernel")
    ap.add_argument("--metrics-json", default="",
                    help="engine: write the registry snapshot as JSON here "
                         "(plus Prometheus text exposition at PATH.prom)")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    help="engine: print one-line stats every N steps "
                         "(0 -> off)")
    ap.add_argument("--profile-steps", type=int, default=0,
                    help="engine: jax.profiler trace window around the "
                         "first N steps (needs --profile-dir)")
    ap.add_argument("--profile-dir", default="",
                    help="directory for jax.profiler traces")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify", action="store_true",
                    help="engine: assert greedy outputs are bit-identical "
                         "to the static path per request")
    args = ap.parse_args()
    if args.packed and not args.ckpt:
        ap.error("--packed requires --ckpt")
    enable_compile_cache()

    cfg = get_tiny_config(args.arch) if args.tiny else get_config(args.arch)
    model = build_model(cfg, remat=False)
    params = _load_params(args, model)
    if args.engine == "continuous":
        _serve_engine(args, cfg, model, params)
    else:
        _serve_static(args, cfg, model, params)


if __name__ == "__main__":
    main()
