"""Continuous-batching serving engine: variable-length requests in fixed
device slots over a paged KV pool, no recompilation after warmup.

The engine owns a fixed pool of ``page_size``-token KV pages per layer
(``serving/kv_cache.py``; dense or INT8 per-head-group quantized), routed
to each slot through a host-side block table (``serving/paging.py`` owns
the refcounted allocator, ``serving/prefix_cache.py`` the shared-prefix
index), and three jitted step functions:

- **prefill** (one compile per (prompt bucket, batch bucket) pair): runs
  the model over a whole admission batch of right-padded prompts against
  a fresh dense (L, B, W) mini-cache, gathers logits at each row's true
  last token, samples the first output tokens on device, and scatters the
  B mini-caches into the admitted slots' pages through per-row page maps
  in ONE dispatch (``write_pages``; batch sizes round up to pow2 batch
  buckets, padding rows carry all-sentinel maps so their writes are
  dropped);
- **chunk** (one compile, ever): one bucket-width chunk of a prompt's
  unmatched suffix — a prefix-cache hit, or a prompt LONGER than the
  largest bucket — written into the slot's pages at [start, start+W)
  while the queries attend the pool through the block table under the
  offset causal mask (``model.prefill_chunk``), so max_len-scale prompts
  serve without a max_len-wide compile;
- **decode** (one compile, ever): one token for ALL slots at once — each
  slot reads and writes the pool at its own position (``pos`` is a
  vector) through its table row, per-slot sampling params ride along as
  arrays, and exactly one (token, finite-flag) pair per slot crosses the
  device boundary per step.

The host-side :class:`~repro.serving.scheduler.Scheduler` feeds it: FIFO
admission onto the slot free-list (a head-run of same-bucket arrivals
costs one prefill dispatch instead of B), prompt-length bucketing (the
only shape degree of freedom besides the batch bucket), retire-on-
completion. Parked slots carry all-sentinel table rows, so their decode
writes are dropped. On top of the indirection ride copy-free shared-prefix
admission (a prefix cache maps page-aligned token prefixes to live pages;
only the suffix prefills) and preempt-and-resume (under page pressure the
youngest request's pages spill to host memory and its ResumeTicket
re-enters the queue by seq).

`launch/serve.py --engine continuous` drives it;
`benchmarks/engine_bench.py` load-tests it on the CPU (Zipf, burst,
long-prompt, shared-prefix, and overload/preemption traces). The
``results/BENCH_*.json`` files CI writes from those scripts are CPU and
interpreter artifacts, not speed claims; the chip benchmark is
``bench/run.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import MetricsRegistry, Trace
from repro.obs.spans import scope, span, step_span
from repro.serving.kv_cache import (cache_bytes, init_paged_storage,
                                    write_pages)
from repro.serving.paging import (PageAllocator, restore_pages, spill_pages)
from repro.serving.prefix_cache import PrefixCache
from repro.serving.sampling import sample_tokens
from repro.serving.scheduler import (DuplicateRequestError,
                                     EngineInvariantError, EngineStalledError,
                                     GenerationRequest, GenerationResult,
                                     InvalidRequestError, QueueFullError,
                                     RequestStatus, ResumeTicket, Scheduler)


def step_jit(fn, **jit_kwargs):
    """``jax.jit(fn)`` with every dot traced inside at float32 precision
    (``"highest"``): XLA's dots and those inside the Pallas kernels
    (``dequant_matmul``, ``flash_decode``), which read the same default when
    they are traced. On TPU that is several MXU passes per f32 dot.

    At TPU default precision XLA rounds f32 dot operands to bf16 on the MXU
    but computes a one-row dot in f32, so a request's logits would depend
    on how many rows share its batch (the prefill batch bucket, the static
    path's batch of one) and greedy tokens could flip on near-ties. Every
    serving step program, the engine's and the static path's, is built
    here."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return jax.jit(traced, **jit_kwargs)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine shape/storage policy. The KV cache is a fixed pool of
    ``page_size``-token pages routed through per-slot block tables
    (``num_pages=0`` → num_slots · ceil(max_len/page_size): every slot can
    hold max_len tokens at once). ``kv_quantized`` switches the pool to
    INT8 per-head-group storage (``kv_group_size=0`` → one group per
    head); ``prompt_buckets=()`` → power-of-two buckets covering max_len.
    A custom ``prompt_buckets`` whose largest bucket is smaller than
    max_len turns prompts beyond it into chunked prefills.

    ``prefix_caching`` enables copy-free shared-prefix admission;
    preempt-and-resume under page pressure is always on. Greedy output is
    bit-identical to the static path when ``page_size`` divides
    ``max_len`` (otherwise the gathered cache view is wider than max_len
    and reduction shapes differ by masked-out zeros). ``kv_layout`` has
    one legal value, ``"paged"`` (the slot layout was removed); the field
    stays for configuration files that name it. ``mixed_admission`` lets
    one prefill dispatch admit a FIFO head-run that crosses prompt buckets
    (right-padded to the largest member's bucket) — fewer dispatches,
    identical outputs.

    ``max_queue`` bounds the scheduler backlog: a submit that would push
    the queue past it raises :class:`QueueFullError` (load shedding;
    ``try_submit`` converts the raise into a terminal ``rejected``
    result). 0 → unbounded. ``stall_patience`` is how many consecutive
    no-progress steps :meth:`Engine.run` tolerates with work outstanding
    before raising :class:`EngineStalledError` (early deadlock
    detection).

    ``use_fused_decode`` (default on) routes the decode step's cache read
    through the fused Pallas flash-decode kernel — INT8 codes dequantize
    in-tile, per-slot positions bound the K loop, the block tables gather
    in the kernel — instead of gathering/dequantizing the whole cache then
    attending. ``False`` is the escape hatch back to the reference
    dequant-then-attend path (bit-exact pre-fusion numerics)."""
    num_slots: int = 8
    max_len: int = 256
    prompt_buckets: tuple = ()
    kv_dtype: Any = jnp.float32
    kv_quantized: bool = False
    kv_group_size: int = 0
    max_top_k: int = 64
    kv_layout: str = "paged"           # the only layout
    page_size: int = 16
    num_pages: int = 0                 # 0 → max_len for every slot
    prefix_caching: bool = True
    mixed_admission: bool = False      # cross-bucket admission runs
    max_queue: int = 0                 # 0 → unbounded backlog
    stall_patience: int = 8            # no-progress steps before stalling
    use_fused_decode: bool = True      # fused flash-decode cache reads
    queue_trace_samples: int = 4096    # queue-depth ring-buffer capacity

    def __post_init__(self):
        if self.kv_layout != "paged":
            raise ValueError(
                f"kv_layout {self.kv_layout!r}: the engine serves from the "
                f"paged pool only (the slot layout was removed); use "
                f"'paged' or leave the field at its default")


def batch_buckets(num_slots: int) -> tuple:
    """Power-of-two prefill batch buckets 1, 2, … covering num_slots."""
    out, b = [], 1
    while b < num_slots:
        out.append(b)
        b *= 2
    out.append(b)
    return tuple(out)


def _counter_view(key: str):
    """Legacy integer counter attribute backed by a registry child."""
    return property(lambda self: int(self._c[key].value))


class Engine:
    """Continuous batching over a paged KV pool and fixed-shape step
    programs."""

    def __init__(self, model, params, cfg: EngineConfig = EngineConfig(),
                 faults=None, registry: Optional[MetricsRegistry] = None):
        mcfg = model.cfg
        if mcfg.family not in ("dense", "moe") or mcfg.frontend:
            raise ValueError(
                f"engine serves token-LM families (dense/moe), got "
                f"{mcfg.family}/{mcfg.frontend}")
        self.model, self.params, self.cfg = model, params, cfg
        self.faults = faults               # FaultPlan | None (set_faults)
        self.scheduler = Scheduler(cfg.num_slots, cfg.max_len,
                                   cfg.prompt_buckets)
        self.batch_buckets = batch_buckets(cfg.num_slots)
        s, pg = cfg.num_slots, cfg.page_size
        if pg < 1:
            raise ValueError(f"page_size must be >= 1, got {pg}")
        self.pages_per_slot = -(-cfg.max_len // pg)
        num_pages = cfg.num_pages or s * self.pages_per_slot
        if num_pages < self.pages_per_slot:
            # the oldest request is unpreemptable; it must always be able
            # to grow to max_len or admission can deadlock
            raise ValueError(
                f"num_pages {num_pages} < pages_per_slot "
                f"{self.pages_per_slot}: one max_len request must fit")
        self.kv = init_paged_storage(
            mcfg, num_pages, pg, dtype=cfg.kv_dtype,
            quantized=cfg.kv_quantized, group_size=cfg.kv_group_size)
        self.alloc = PageAllocator(num_pages, faults=faults)
        self.prefix = (PrefixCache(pg, self.alloc)
                       if cfg.prefix_caching else None)
        # block tables are host state; rows ride to the device as plain
        # int32 data each dispatch (sentinel == num_pages everywhere a slot
        # has no page: parked slots' decode writes are dropped)
        self._table = np.full((s, self.pages_per_slot), num_pages, np.int32)
        self._slot_pages: List[List[int]] = [[] for _ in range(s)]
        self._pos = np.zeros(s, np.int32)
        self._tok = np.zeros(s, np.int32)
        self._temps = np.zeros(s, np.float32)
        self._topks = np.zeros(s, np.int32)
        self._seeds = np.zeros(s, np.uint32)
        self._steps = np.zeros(s, np.uint32)
        self._results: Dict[int, GenerationResult] = {}
        self._done: List[GenerationResult] = []
        self.metrics = registry if registry is not None else MetricsRegistry()
        self._init_metrics()
        self._reset_counters()
        self._prefill, self._chunk, self._decode = self._make_step_fns()

    def _init_metrics(self) -> None:
        """Register the engine's metric families and bind one child per
        family under this engine's constant labels. All dispatch and
        utilization counters live here; the legacy integer attributes
        (``engine.decode_steps`` …) are read-only views over the children,
        and ``queue_stats``/``page_stats`` are views over the same state."""
        m = self.metrics
        cfg = self.cfg
        self._mlabels = {"layout": cfg.kv_layout,
                         "kv": "int8" if cfg.kv_quantized else "dense"}
        names = tuple(sorted(self._mlabels))
        self._metric_children: List[Any] = []

        def counter(key: str, help: str):
            fam = m.counter(f"engine_{key}_total", help, labelnames=names)
            child = fam.labels(**self._mlabels)
            self._metric_children.append(child)
            return child

        self._c = {k: counter(k, h) for k, h in (
            ("decode_steps", "decode dispatches"),
            ("active_slot_steps", "slot-steps that carried a live request"),
            ("prefill_dispatches", "batched-prefill device calls"),
            ("prefill_admitted", "requests admitted via batched prefill"),
            ("prefill_tokens", "prompt tokens run through the prefill and "
                               "chunk programs after prefix reuse"),
            ("chunk_dispatches", "chunked-prefill device calls"),
            ("chunked_admitted", "requests admitted via chunking"),
            ("prefix_hits", "admissions with a cached prefix"),
            ("prefix_misses", "admissions without one (paged only)"),
            ("prefix_hit_tokens", "prompt tokens skipped via prefix reuse"),
            ("preemptions", "requests spilled under page pressure"),
            ("resumes", "tickets restored onto a slot"),
            ("pages_spilled", "pages round-tripped through host memory"),
            ("rejected", "try_submit load-shed rejections"),
        )}
        self._g_queue = m.gauge(
            "engine_queue_depth", "scheduler backlog, sampled per step",
            labelnames=names,
            trace_capacity=cfg.queue_trace_samples).labels(**self._mlabels)
        self._g_slots = m.gauge(
            "engine_slots_active", "slots holding a live request",
            labelnames=names).labels(**self._mlabels)
        self._g_pages = m.gauge(
            "engine_pages_in_use", "allocated KV pages (paged layout)",
            labelnames=names).labels(**self._mlabels)
        self._g_prefix_pages = m.gauge(
            "engine_prefix_cached_pages", "pages held by the prefix cache",
            labelnames=names).labels(**self._mlabels)
        self._metric_children += [self._g_queue, self._g_slots,
                                  self._g_pages, self._g_prefix_pages]

        def hist(key: str, help: str):
            fam = m.histogram(key, help, labelnames=names, unit="seconds")
            child = fam.labels(**self._mlabels)
            self._metric_children.append(child)
            return child

        self._h_queue = hist("request_queue_seconds",
                             "submit to first admission")
        self._h_ttft = hist("request_ttft_seconds",
                            "submit to first generated token")
        self._h_tpot = hist("request_tpot_seconds",
                            "mean seconds per generated token after the first")
        self._h_latency = hist("request_latency_seconds",
                               "submit to terminal status")
        # per-status counters bind their children lazily (statuses appear
        # as the trace produces them); families registered up front
        m.counter("engine_requests_total", "terminal results by status",
                  labelnames=names + ("status",))
        m.counter("engine_tokens_generated_total",
                  "generated tokens in terminal results by status",
                  labelnames=names + ("status",))
        self._status_children: Dict[tuple, Any] = {}

    def _status_counter(self, name: str, status: str):
        child = self._status_children.get((name, status))
        if child is None:
            fam = self.metrics.counter(f"engine_{name}_total")
            child = fam.labels(status=status, **self._mlabels)
            self._status_children[(name, status)] = child
            self._metric_children.append(child)
        return child

    def _reset_counters(self) -> None:
        for child in self._metric_children:
            child.reset()
        self.alloc.peak_in_use = self.alloc.pages_in_use
        self.alloc.alloc_calls = 0
        self.alloc.alloc_failures = 0

    # legacy counter attributes — read-only views over the registry
    decode_steps = _counter_view("decode_steps")
    active_slot_steps = _counter_view("active_slot_steps")
    prefill_dispatches = _counter_view("prefill_dispatches")
    prefill_admitted = _counter_view("prefill_admitted")
    chunk_dispatches = _counter_view("chunk_dispatches")
    chunked_admitted = _counter_view("chunked_admitted")
    prefix_hits = _counter_view("prefix_hits")
    prefix_misses = _counter_view("prefix_misses")
    prefix_hit_tokens = _counter_view("prefix_hit_tokens")
    preemptions = _counter_view("preemptions")
    resumes = _counter_view("resumes")
    pages_spilled = _counter_view("pages_spilled")
    rejected = _counter_view("rejected")

    @property
    def queue_depth_peak(self) -> int:
        return int(self._g_queue.peak)

    def set_faults(self, plan) -> None:
        """Attach/replace the :class:`~repro.serving.faults.FaultPlan`
        (engine hooks AND the page allocator). Attach AFTER :meth:`warmup`
        so scripted fault steps count from the first real step (warmup
        disables injection regardless)."""
        self.faults = plan
        self.alloc.faults = plan

    def _now(self) -> float:
        """Engine clock: the fault plan's virtual clock when one with
        ``slow_step_s`` is attached (deterministic deadline tests),
        wall-clock otherwise."""
        return (self.faults.now() if self.faults is not None
                else time.perf_counter())

    # -- jitted steps ------------------------------------------------------
    def _make_step_fns(self):
        """The three step programs. Prefill fills a dense mini-cache and
        scatters it into the pools through per-row page maps
        (``write_pages``); chunk and decode route every cache access
        through a block table (in-tile paged flash / fused or page-gathered
        decode read), at each layer's index of the stacked pools."""
        model, cfg = self.model, self.cfg
        if getattr(model, "use_fused_decode", None) != cfg.use_fused_decode:
            # the flag lives on the model dataclass (it's baked into the
            # decode trace); rebind a per-engine copy, never mutate the
            # caller's model
            model = dataclasses.replace(
                model, use_fused_decode=cfg.use_fused_decode)
        mcfg = model.cfg
        mini_dtype = jnp.float32 if cfg.kv_quantized else cfg.kv_dtype
        pg = cfg.page_size

        def prefill_fn(params, kv, tokens, lengths, page_maps, temps, topks,
                       seeds):
            b, w = tokens.shape
            with scope("kv_splice"):
                zeros = jnp.zeros((mcfg.num_layers, b, w, mcfg.num_kv_heads,
                                   mcfg.resolved_head_dim), mini_dtype)
                mini = {"k": zeros, "v": zeros,
                        "pos": jnp.zeros((), jnp.int32)}
            logits, mini = model.prefill_at(params, {"tokens": tokens},
                                            mini, lengths=lengths)
            with scope("sample"):
                toks = sample_tokens(logits[:, 0, :], temps, topks, seeds,
                                     jnp.zeros((b,), jnp.uint32),
                                     max_top_k=cfg.max_top_k)
            with scope("kv_splice"):
                kv = write_pages(kv, page_maps, mini["k"], mini["v"], pg)
            return toks, kv

        def chunk_fn(params, kv, tokens, start, length, table_row, temp,
                     topk, seed):
            cache = {"k": kv["k"], "v": kv["v"], "pos": start,
                     "table": table_row}
            logits, cache = model.prefill_chunk(params, {"tokens": tokens},
                                                cache, lengths=length[None])
            with scope("sample"):
                tok = sample_tokens(logits[:, 0, :], temp[None], topk[None],
                                    seed[None], jnp.zeros((1,), jnp.uint32),
                                    max_top_k=cfg.max_top_k)
            return tok[0], {"k": cache["k"], "v": cache["v"]}

        def decode_fn(params, kv, pos, tokens, temps, topks, seeds, steps,
                      tables):
            cache = {"k": kv["k"], "v": kv["v"], "pos": pos,
                     "table": tables}
            logits, cache = model.decode_step(params, tokens, cache)
            with scope("sample"):
                tok = sample_tokens(logits[:, 0, :], temps, topks, seeds,
                                    steps, max_top_k=cfg.max_top_k)
                # finite-logit flag rides along in the same int32 transfer:
                # a slot whose logits went non-finite fails ALONE on the host
                ok = jnp.all(jnp.isfinite(logits[:, 0, :]), axis=-1)
                out = jnp.stack([tok.astype(jnp.int32),
                                 ok.astype(jnp.int32)], axis=-1)
            return out, {"k": cache["k"], "v": cache["v"]}

        return (step_jit(prefill_fn, donate_argnums=1),
                step_jit(chunk_fn, donate_argnums=1),
                step_jit(decode_fn, donate_argnums=1))

    # -- request API -------------------------------------------------------
    def submit(self, req: GenerationRequest) -> None:
        """Enqueue a request. Raises typed, caller-distinguishable errors:
        :class:`DuplicateRequestError` for an rid already in flight (the
        scheduler would otherwise silently overwrite its result),
        :class:`InvalidRequestError` for requests that can NEVER be
        admitted (bad shape, or a prompt needing more pages than the whole
        pool), :class:`QueueFullError` when ``max_queue`` would be
        exceeded. See :meth:`try_submit` for shed-as-result semantics."""
        if req.rid in self._results:
            raise DuplicateRequestError(
                f"request rid={req.rid} is already in flight")
        need = -(-req.prompt_len // self.cfg.page_size)
        if need > self.alloc.num_pages:
            raise InvalidRequestError(
                f"request rid={req.rid}: prompt needs {need} pages but the "
                f"pool has {self.alloc.num_pages} — can never be admitted")
        if (self.cfg.max_queue > 0 and req.rid >= 0
                and len(self.scheduler.queue) >= self.cfg.max_queue):
            # negative rids are warmup clones — internal, never shed
            raise QueueFullError(
                f"request rid={req.rid} rejected: queue at "
                f"max_queue={self.cfg.max_queue}")
        self.scheduler.submit(req)
        now = self._now()
        res = GenerationResult(rid=req.rid, prompt_len=req.prompt_len,
                               tokens=[], t_enqueue=now)
        if self.metrics.enabled:
            res.trace = Trace(req.rid, now)
        self._results[req.rid] = res

    def try_submit(self, req: GenerationRequest) -> bool:
        """Load-shedding submit: capacity/validity rejections become a
        terminal result with ``status == "rejected"`` (surfaced by
        :meth:`run` like any other completion) instead of an exception.
        Duplicate rids still raise — shedding a duplicate would emit two
        results for one rid."""
        try:
            self.submit(req)
            return True
        except DuplicateRequestError:
            raise
        except (QueueFullError, InvalidRequestError) as e:
            now = self._now()
            res = GenerationResult(
                rid=req.rid, prompt_len=req.prompt_len, tokens=[],
                t_enqueue=now, t_finish=now,
                status=RequestStatus.REJECTED.value,
                finish_reason=RequestStatus.REJECTED.value, error=str(e))
            if self.metrics.enabled:
                res.trace = Trace(req.rid, now)
            self._c["rejected"].inc()
            self._observe_terminal(res)
            self._done.append(res)
            return False

    def cancel(self, rid: int) -> bool:
        """Cancel an in-flight request. Queued requests (and preempted
        tickets — their spilled host payload dies with the ticket) leave
        the queue; a running request is failed out of its slot with its
        pages reclaimed. Either way the partial tokens generated so far
        are emitted in a terminal ``cancelled`` result. False when the rid
        is unknown or already finished."""
        if rid not in self._results:
            return False
        item = self.scheduler.remove(rid)
        if item is not None:
            self._finish_queued(item, RequestStatus.CANCELLED.value)
            return True
        for slot in self.scheduler.active_slots():
            if self.scheduler.slots[slot].request.rid == rid:
                self._fail_slot(slot, RequestStatus.CANCELLED.value)
                return True
        return False

    def _expire_deadlines(self) -> None:
        """Terminal-fail every request whose ``deadline_s`` has elapsed
        since submit — queued requests shed without ever running; running
        requests keep the tokens they produced. Called at each step
        boundary, so expiry lags the deadline by at most one step."""
        now = self._now()
        sched = self.scheduler
        expired = []
        for item in sched.queue:
            req = item.request if isinstance(item, ResumeTicket) else item
            if (req.deadline_s > 0 and req.rid in self._results
                    and now - self._results[req.rid].t_enqueue
                    >= req.deadline_s):
                expired.append(req.rid)
        for rid in expired:
            self._finish_queued(sched.remove(rid),
                                RequestStatus.DEADLINE.value)
        for slot in list(sched.active_slots()):
            req = sched.slots[slot].request
            if (req.deadline_s > 0
                    and now - self._results[req.rid].t_enqueue
                    >= req.deadline_s):
                self._fail_slot(slot, RequestStatus.DEADLINE.value)

    def warmup(self, reqs) -> Dict[str, int]:
        """Compile every program a trace shaped like ``reqs`` can hit
        before timing starts:

        - for each distinct prompt bucket in ``reqs``, the full
          (bucket × batch-bucket) prefill grid, traced with all-padding
          dummy batches (all-sentinel page maps, so every cache write is
          dropped);
        - the chunked-prefill program (one dummy chunk) if any request's
          prompt exceeds the largest bucket, or a prefix hit could stream
          a suffix through it;
        - the decode program, via one short clone per distinct bucket
          (prompt clipped to max_len - 1 so the clone's >= 1-token budget
          always fits) plus a minimal 2-token fallback request if none of
          the clones had decode headroom.

        Warmup requires an IDLE engine: it drains the scheduler, so real
        requests submitted beforehand would be silently executed and their
        results discarded — it raises instead. Clones use negative rids
        (callers' traces use non-negative ones) and are filtered from the
        caller-visible results explicitly. Resets the dispatch/utilization
        counters and returns the post-warmup :meth:`compile_counts`
        snapshot."""
        if not self.scheduler.idle:
            raise RuntimeError(
                "Engine.warmup on a non-idle engine: warmup drains the "
                "scheduler, which would silently execute and discard "
                "already-submitted requests — warm up first, then submit")
        plan = self.faults
        self.set_faults(None)          # warmup always runs fault-free
        wmax = self.scheduler.buckets[-1]
        seen: Dict[int, GenerationRequest] = {}
        chunked = False
        for r in reqs:
            if r.prompt_len > wmax:
                chunked = True
            else:
                seen.setdefault(self.scheduler.bucket_for(r.prompt_len), r)

        # (bucket × batch-bucket) prefill grid: all-padding dummy batches
        # whose all-sentinel page maps drop every cache write
        for w in sorted(seen):
            for bb in self.batch_buckets:
                tok_dev, self.kv = self._prefill(
                    self.params, self.kv,
                    jnp.zeros((bb, w), jnp.int32),
                    jnp.ones((bb,), jnp.int32),
                    jnp.full((bb, -(-w // self.cfg.page_size)),
                             self.alloc.num_pages, jnp.int32),
                    jnp.zeros((bb,), jnp.float32),
                    jnp.zeros((bb,), jnp.int32),
                    jnp.zeros((bb,), jnp.uint32))
        # with prefix caching the chunk program serves every prefix hit,
        # not just beyond-largest-bucket prompts — warm it whenever a trace
        # could hit it
        if chunked or (self.prefix is not None and seen):
            # one dummy chunk compiles the (single) chunk program
            tok_dev, self.kv = self._chunk(*self._dummy_chunk_args())

        # end-to-end clones (decode program + host bookkeeping paths)
        wid = -1
        decode_warmed = False
        for _, r in sorted(seen.items()):
            plen = min(r.prompt_len, self.cfg.max_len - 1)
            nnew = min(2, self.cfg.max_len - plen)     # >= 1 by construction
            decode_warmed |= nnew >= 2
            self.submit(GenerationRequest(rid=wid, prompt=r.prompt[:plen],
                                          max_new_tokens=nnew,
                                          sampling=r.sampling))
            wid -= 1
        if (seen or chunked) and not decode_warmed:
            self.submit(GenerationRequest(
                rid=wid, prompt=np.asarray([1], np.int32), max_new_tokens=2))
        real = [r for r in self.run() if r.rid >= 0]
        self._done.extend(real)        # unreachable under the idle guard
        if self.prefix is not None:
            # the clones seeded the prefix cache with warmup prompts —
            # drop them so runtime hit/miss stats start clean and the
            # first real admissions aren't served warmup pages
            self.prefix.clear()
        assert self.alloc.pages_in_use == 0, \
            f"warmup leaked {self.alloc.pages_in_use} pages"
        self._reset_counters()
        self.set_faults(plan)
        return self.compile_counts()

    def _dummy_chunk_args(self) -> tuple:
        """Chunk-program arguments that write nothing live: an
        all-sentinel table row drops the writes outright."""
        return (self.params, self.kv,
                jnp.zeros((1, self.scheduler.buckets[-1]), jnp.int32),
                np.int32(0), np.int32(1),
                jnp.full((1, self.pages_per_slot), self.alloc.num_pages,
                         jnp.int32),
                np.float32(0.0), np.int32(0), np.uint32(0))

    def _decode_args(self) -> tuple:
        return (self.params, self.kv, jnp.asarray(self._pos),
                jnp.asarray(self._tok[:, None]), jnp.asarray(self._temps),
                jnp.asarray(self._topks), jnp.asarray(self._seeds),
                jnp.asarray(self._steps), jnp.asarray(self._table))

    def step(self) -> None:
        """Admit every admissible request (one batched prefill dispatch per
        FIFO head-run, chunked prefill for beyond-largest-bucket prompts
        and prefix-hit suffixes, page restoration for resume tickets), then
        run one decode step for all slots.

        Failure-atomic: a fault anywhere in the step (failed spill or
        restore, injected allocation failure, non-finite decode logits, a
        prefill dispatch raising) terminal-fails ONLY the culpable
        request(s), rolls their page/slot acquisitions back, and leaves the
        rest of the batch serving — :meth:`check_invariants` holds at every
        step boundary."""
        with step_span(self.decode_steps):
            self._step()

    def _step(self) -> None:
        sched = self.scheduler
        with span("engine.expire"):
            if self.faults is not None:
                self.faults.tick()
            self._expire_deadlines()
            # ring-buffered backlog sample: peak/mean/samples accumulate in
            # the gauge child, the trace keeps the most recent
            # queue_trace_samples values and counts overwrites in
            # queue_stats()["dropped"]
            self._g_queue.set(len(sched.queue))
        with span("engine.admit"):
            self._admit()

        if sched.num_active == 0:
            return
        with span("engine.extend"):
            self._extend_for_decode()
        if sched.num_active == 0:          # extension self-preempted all
            return
        with span("engine.decode", self._decode_span_args):
            out_dev, self.kv = self._decode(*self._decode_args())
        with span("engine.decode.wait"):
            out = np.asarray(out_dev)  # lint: allow[host-sync] THE one transfer per decode step (S, 2): token + finite flag
        with span("engine.commit"):
            self._commit(out)

    def _decode_span_args(self) -> dict:
        """``engine.decode``'s arguments: the slots it decodes and their
        live context (each attends its position + 1 tokens)."""
        active = self.scheduler.active_slots()
        return {"rows": len(active),
                "ctx_tokens": int(self._pos[active].sum()) + len(active)}

    def _commit(self, out: np.ndarray) -> None:
        """Take one decode step's (S, 2) tokens and finite flags into the
        requests' results; finish or fail slots."""
        sched = self.scheduler
        toks, finite = out[:, 0], out[:, 1]
        now = self._now()
        self._c["decode_steps"].inc()
        self._c["active_slot_steps"].inc(sched.num_active)
        for slot in list(sched.active_slots()):
            state = sched.slots[slot]
            rid = state.request.rid
            bad = not finite[slot]
            if self.faults is not None and self.faults.poison_logits(rid):
                bad = True
            if bad:
                # the sampled token is garbage: fail this slot alone, keep
                # the rest of the batch decoding
                self._fail_slot(slot, RequestStatus.ERROR.value,
                                "non-finite decode logits")
                continue
            tok = int(toks[slot])
            state.generated += 1
            self._results[rid].tokens.append(tok)
            self._pos[slot] += 1
            self._tok[slot] = tok
            self._steps[slot] += 1
            if state.done or tok == state.request.eos_id:
                self._finish(slot, now)

    @staticmethod
    def _prefill_span_args(items, bucket: int) -> dict:
        """``engine.prefill``'s arguments: rows, bucket, prompt tokens."""
        return {"rows": len(items), "bucket": bucket,
                "prompt_tokens": sum(r.prompt_len for _, r in items)}

    def _abort_admission(self, items, exc: Exception) -> None:
        """A prefill dispatch raised mid-admission: terminal-fail exactly
        the requests it was admitting (their slots/pages roll back) and
        keep serving everyone else."""
        for slot, req in items:
            state = self.scheduler.slots[slot]
            if state is not None and state.request.rid == req.rid:
                self._fail_slot(slot, RequestStatus.ERROR.value,
                                f"prefill failed: {exc}")

    # -- admission ---------------------------------------------------------
    def _set_table_row(self, slot: int, pages: List[int]) -> None:
        row = self._table[slot]
        row[:] = self.alloc.num_pages                # sentinel tail
        row[:len(pages)] = pages

    def _acquire_pages(self, n: int, seq: int,
                       allow_preempt: bool) -> Optional[List[int]]:
        """n pages, escalating when the pool is dry: evict unreferenced
        prefix-cache entries first, then (tickets and decode extension
        only) preempt the youngest request no older than ``seq``. None
        when neither escalation can free enough — the caller waits."""
        if n == 0:
            return []
        while True:
            pages = self.alloc.alloc(n)
            if pages is not None:
                return pages
            deficit = n - self.alloc.num_free
            if self.prefix is not None and self.prefix.evict(deficit) > 0:
                continue
            if allow_preempt and self._preempt_youngest(seq):
                continue
            return None

    def _preempt_youngest(self, seq: int) -> bool:
        """Spill the youngest live request with seq >= ``seq`` (self-
        preemption is legal: a decode extension may evict the requester
        itself, which then resumes via its ticket). False when every live
        request is strictly older — the oldest is never preempted, so it
        always runs to completion and admission cannot livelock."""
        victim, vseq = -1, -1
        for slot in self.scheduler.active_slots():
            s = self.scheduler.slots[slot].request.seq
            if s >= seq and s > vseq:
                victim, vseq = slot, s
        if victim < 0:
            return False
        with span("engine.preempt"):
            self._preempt(victim)
        return True

    def _preempt(self, slot: int) -> None:
        """Spill a live slot's pages to host memory, requeue its ticket
        (ordered by seq — ahead of every never-admitted request), release
        its pages and park the slot. Prefix-cached pages keep their cache
        reference: only the request's own references drop."""
        sched = self.scheduler
        state = sched.slots[slot]
        pages = self._slot_pages[slot]
        try:
            if self.faults is not None:
                self.faults.check_spill("spill")
            payload = spill_pages(self.kv, pages)
        except Exception as e:             # noqa: BLE001 — fault isolation
            # the spill never produced a payload, so the victim's cache
            # state is unrecoverable: fail it (partial tokens survive
            # host-side) and reclaim its pages — the pool still frees, so
            # the caller's escalation makes progress either way
            self._fail_slot(slot, RequestStatus.ERROR.value,
                            f"preemption spill failed: {e}")
            return
        ticket = ResumeTicket(request=state.request,
                              generated=state.generated,
                              last_token=int(self._tok[slot]),
                              pos=int(self._pos[slot]),
                              n_pages=len(pages),
                              payload=payload)
        sched.preempt(slot, ticket)
        self._c["preemptions"].inc()
        self._c["pages_spilled"].inc(len(pages))
        res = self._results[state.request.rid]
        if res.trace is not None:
            res.trace.stamp("preempt", self._now())
        self.alloc.decref(pages)
        self._slot_pages[slot] = []
        self._set_table_row(slot, [])
        self._park(slot)

    def _try_resume(self) -> bool:
        """Head-of-queue ResumeTicket → fresh pages + restored payload.
        The spilled bytes scatter back verbatim (raw storage round-trip),
        so the resumed request's decode continues bit-identically. False
        when blocked (no free slot, or pages unobtainable without
        preempting a strictly-older request)."""
        sched = self.scheduler
        ticket = sched.peek()
        if not sched.free:
            return False
        pages = self._acquire_pages(ticket.n_pages, ticket.seq,
                                    allow_preempt=True)
        if pages is None:
            return False
        with span("engine.resume"):
            slot, ticket = sched.admit_head()
            try:
                if self.faults is not None:
                    self.faults.check_spill("restore")
                self.kv = restore_pages(self.kv, pages, ticket.payload,
                                        self.alloc.num_pages)
            except Exception as e:      # noqa: BLE001 — fault isolation
                # the spilled bytes never reached the device: hand the fresh
                # pages back and fail the ticket's request (its pre-preemption
                # tokens survive in the result). Returning True is honest —
                # the ticket reached a terminal state, the queue moved.
                self.alloc.decref(pages)
                self._fail_slot(slot, RequestStatus.ERROR.value,
                                f"resume restore failed: {e}")
                return True
            self._slot_pages[slot] = pages
            self._set_table_row(slot, pages)
            sp = ticket.request.sampling
            self._pos[slot] = ticket.pos
            self._tok[slot] = ticket.last_token
            self._temps[slot] = sp.temperature
            self._topks[slot] = sp.top_k
            self._seeds[slot] = np.uint32(sp.seed)
            self._steps[slot] = ticket.generated   # sampling's fold_in counter
            self._c["resumes"].inc()
            res = self._results[ticket.request.rid]
            if res.trace is not None:
                res.trace.stamp("resume", self._now())
        return True

    def _extend_for_decode(self) -> None:
        """Back every live slot's next write position with a page before
        the decode dispatch (a write through a sentinel entry would drop
        the new token's K/V and corrupt the sampled output). Oldest-first,
        preempting the youngest (possibly the requester itself) when the
        pool is dry."""
        sched = self.scheduler
        pg = self.cfg.page_size
        order = sorted(sched.active_slots(),
                       key=lambda i: sched.slots[i].request.seq)
        for slot in order:
            state = sched.slots[slot]
            if state is None:            # preempted by an older extension
                continue
            need = int(self._pos[slot]) // pg + 1
            have = self._slot_pages[slot]
            if need <= len(have):
                continue
            pages = self._acquire_pages(need - len(have), state.request.seq,
                                        allow_preempt=True)
            if pages is None or sched.slots[slot] is not state:
                # the slot self-preempted while escalating (its ticket
                # resumes later) — hand back anything grabbed after that
                if pages is not None:
                    self.alloc.decref(pages)
                continue
            have.extend(pages)
            self._set_table_row(slot, have)

    def _admit(self) -> None:
        """Admission, strictly FIFO. Plain bucket-size requests
        accumulate into one right-padded prefill dispatch (``pending``);
        prefix hits and beyond-largest-bucket prompts stream their
        unmatched suffix through the chunk program; resume tickets restore
        spilled pages, preempting strictly-younger live requests when the
        pool is short. ``pending`` is always flushed before a resume can
        preempt, so preemption victims are fully prefilled."""
        sched = self.scheduler
        pg = self.cfg.page_size
        wmax = sched.buckets[-1]
        pending: List[tuple] = []            # [(slot, req)], one dispatch
        while sched.free:
            head = sched.peek()
            if head is None:
                break
            if isinstance(head, ResumeTicket):
                self._flush_pending(pending)
                if not self._try_resume():
                    break
                continue
            req = head
            matched, mtok = ([], 0)
            if self.prefix is not None:
                matched, mtok = self.prefix.match(req.prompt)
            fresh = self._acquire_pages(-(-req.prompt_len // pg)
                                        - len(matched),
                                        req.seq, allow_preempt=False)
            if fresh is None:
                # head-of-line waits for pages (never preempts: everything
                # live is older); roll back the prefix references
                if matched:
                    self.alloc.decref(matched)
                break
            if mtok:
                self._c["prefix_hits"].inc()
                self._c["prefix_hit_tokens"].inc(mtok)
            elif self.prefix is not None:
                self._c["prefix_misses"].inc()
            slot, _ = sched.admit_head()
            pages = matched + fresh
            self._slot_pages[slot] = pages
            self._set_table_row(slot, pages)
            if mtok or req.prompt_len > wmax:
                # the flush writes any pending twin's pages before the
                # chunk program reads the matched ones (in-order dispatch)
                self._flush_pending(pending)
                try:
                    self._admit_stream(slot, req, mtok)
                except Exception as e:  # noqa: BLE001 — fault isolation
                    # fail this admission alone; skip the prefix insert
                    # (the pages hold a partially written prompt)
                    self._abort_admission([(slot, req)], e)
                    continue
            else:
                if (pending and not self.cfg.mixed_admission
                        and sched.bucket_for(req.prompt_len)
                        != sched.bucket_for(pending[0][1].prompt_len)):
                    self._flush_pending(pending)
                pending.append((slot, req))
            if self.prefix is not None:
                self.prefix.insert(req.prompt, pages)
        self._flush_pending(pending)

    def _flush_pending(self, pending: List[tuple]) -> None:
        """One right-padded prefill dispatch for the accumulated admission
        run (padding rows carry all-sentinel page maps)."""
        if not pending:
            return
        try:
            with span("engine.prefill", lambda: self._prefill_span_args(
                    pending, max(self.scheduler.bucket_for(r.prompt_len)
                                 for _, r in pending))):
                self._dispatch_pending(pending)
        except Exception as e:             # noqa: BLE001 — fault isolation
            self._abort_admission(pending, e)
        del pending[:]

    def _dispatch_pending(self, pending: List[tuple]) -> None:
        t_admit = self._now()
        b = len(pending)
        w = max(self.scheduler.bucket_for(r.prompt_len) for _, r in pending)
        bb = next(x for x in self.batch_buckets if b <= x)
        pg = self.cfg.page_size
        sentinel = self.alloc.num_pages
        tokens = np.zeros((bb, w), np.int32)
        lengths = np.ones((bb,), np.int32)
        maps = np.full((bb, -(-w // pg)), sentinel, np.int32)
        temps = np.zeros((bb,), np.float32)
        topks = np.zeros((bb,), np.int32)
        seeds = np.zeros((bb,), np.uint32)
        for i, (slot, req) in enumerate(pending):
            tokens[i, :req.prompt_len] = req.prompt
            lengths[i] = req.prompt_len
            maps[i, :len(self._slot_pages[slot])] = self._slot_pages[slot]
            sp = req.sampling
            temps[i], topks[i] = sp.temperature, sp.top_k
            seeds[i] = np.uint32(sp.seed)
        tok_dev, self.kv = self._prefill(
            self.params, self.kv, jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(maps), jnp.asarray(temps), jnp.asarray(topks),
            jnp.asarray(seeds))
        toks = np.asarray(tok_dev)  # lint: allow[host-sync] one transfer per prefill dispatch
        self._c["prefill_dispatches"].inc()
        self._c["prefill_admitted"].inc(b)
        self._c["prefill_tokens"].inc(int(lengths[:b].sum()))
        now = self._now()
        for i, (slot, req) in enumerate(pending):
            self._record_first_token(slot, req, int(toks[i]), now, t_admit)

    def _admit_stream(self, slot: int, req: GenerationRequest,
                      start_tok: int) -> None:
        """Stream a prompt's unmatched suffix through the chunk program,
        starting at the prefix-matched offset (0 for a plain
        beyond-largest-bucket prompt). Only the final chunk's sample is
        real; intermediate device results are never synced."""
        t_admit = self._now()
        w = self.scheduler.buckets[-1]
        p, sp = req.prompt_len, req.sampling
        table_row = jnp.asarray(self._table[slot:slot + 1])
        tok_dev = None
        for start in range(start_tok, p, w):
            clen = min(w, p - start)
            chunk = np.zeros((1, w), np.int32)
            chunk[0, :clen] = req.prompt[start:start + clen]
            with span("engine.chunk", lambda: {"tokens": clen}):
                tok_dev, self.kv = self._chunk(
                    self.params, self.kv, jnp.asarray(chunk),
                    np.int32(start), np.int32(clen), table_row,
                    np.float32(sp.temperature), np.int32(sp.top_k),
                    np.uint32(sp.seed))
            self._c["chunk_dispatches"].inc()
            self._c["prefill_tokens"].inc(clen)
        self._c["chunked_admitted"].inc()
        # lint: allow[host-sync] one scalar per chunked prefill, by design
        self._record_first_token(slot, req, int(tok_dev), self._now(),
                                 t_admit)

    def _record_first_token(self, slot: int, req: GenerationRequest,
                            tok: int, now: float,
                            t_admit: Optional[float] = None) -> None:
        res = self._results[req.rid]
        res.t_admit = now if t_admit is None else t_admit
        res.t_first_token = now
        if res.trace is not None:
            res.trace.stamp("admitted", res.t_admit)
            res.trace.stamp("first_token", now)
        res.tokens.append(tok)
        state = self.scheduler.slots[slot]
        state.generated = 1
        sp = req.sampling
        self._pos[slot] = req.prompt_len
        self._tok[slot] = tok
        self._temps[slot] = sp.temperature
        self._topks[slot] = sp.top_k
        self._seeds[slot] = np.uint32(sp.seed)
        self._steps[slot] = 1
        if state.done or tok == req.eos_id:
            self._finish(slot, now)

    def _finish(self, slot: int, now: float) -> None:
        req = self.scheduler.retire(slot)
        res = self._results.pop(req.rid)
        res.t_finish = now
        res.status = RequestStatus.OK.value
        res.finish_reason = (RequestStatus.EOS.value
                             if res.tokens and res.tokens[-1] == req.eos_id
                             else RequestStatus.LENGTH.value)
        self._observe_terminal(res)
        self._done.append(res)
        self._release_slot(slot)

    def _fail_slot(self, slot: int, status: str, msg: str = "") -> None:
        """Terminal-fail a LIVE slot: retire it, reclaim its pages, park
        it, and emit the partial-token result with ``status``. The
        crash-safe reclamation primitive — cancel, deadline expiry, and
        step-level fault isolation all land here, so a failing request can
        never leak pages or wedge its slot."""
        req = self.scheduler.retire(slot)
        res = self._results.pop(req.rid)
        res.t_finish = self._now()
        res.status = status
        res.finish_reason = status
        res.error = msg
        self._observe_terminal(res)
        self._done.append(res)
        self._release_slot(slot)

    def _release_slot(self, slot: int) -> None:
        # release the request's page references; prefix-cached pages keep
        # their cache reference and survive for future matches
        self.alloc.decref(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self._set_table_row(slot, [])
        self._park(slot)

    def _finish_queued(self, item, status: str, msg: str = "") -> None:
        """Terminal a request that never reached (or was preempted off) a
        slot: queued requests carry no device state; a ResumeTicket's pages
        were already released at preemption and its spilled host payload
        dies with the ticket. Partial tokens accumulated before a
        preemption are still in the result and are emitted."""
        req = item.request if isinstance(item, ResumeTicket) else item
        res = self._results.pop(req.rid)
        res.t_finish = self._now()
        res.status = status
        res.finish_reason = status
        res.error = msg
        self._observe_terminal(res)
        self._done.append(res)

    def _observe_terminal(self, res: GenerationResult) -> None:
        """Terminal lifecycle observations: close the trace, count the
        result by status, feed the latency histograms. Pure host dict/float
        work — no device interaction, safe inside the step loop."""
        if res.trace is not None:
            res.trace.finish(res.status, res.t_finish)
        self._status_counter("requests", res.status).inc()
        if res.tokens:
            self._status_counter("tokens_generated",
                                 res.status).inc(len(res.tokens))
        if not self.metrics.enabled:
            return
        if res.status != RequestStatus.REJECTED.value:
            # rejected requests never entered the queue; everyone else gets
            # a queue-time sample (whole lifetime when never admitted)
            self._h_queue.observe(res.queue_time)
        if res.t_first_token > 0.0:
            self._h_ttft.observe(res.ttft)
            if len(res.tokens) > 1:
                self._h_tpot.observe(res.tpot)
        self._h_latency.observe(res.latency)

    def _park(self, slot: int) -> None:
        # park the freed slot: greedy token 0 at position 0; its
        # all-sentinel table row drops its parked decode writes
        self._pos[slot] = 0
        self._tok[slot] = 0
        self._temps[slot] = 0.0
        self._topks[slot] = 0
        self._seeds[slot] = 0
        self._steps[slot] = 0

    def run(self, max_steps: int = 1_000_000,
            step_hook=None) -> List[GenerationResult]:
        """Drive until every submitted request reaches a terminal status;
        returns results in completion order. ``step_hook(engine)``, when
        given, runs after every step (periodic stats printing, profiler
        windows) — host-side only, it must not submit or cancel.

        Raises :class:`EngineStalledError` — carrying the stuck requests'
        rids and where they are stuck — in two cases: ``max_steps``
        exhausted with work outstanding, or (early deadlock detection)
        ``cfg.stall_patience`` consecutive steps made NO progress (no
        decode, no admission, no resume, no completion) while work remains.
        A decode step always counts as progress, so patience only burns
        while the engine spins on an unadmittable queue."""
        sched = self.scheduler
        stalled = 0
        for _ in range(max_steps):
            if sched.idle:
                break
            before = (self.decode_steps, self.prefill_admitted,
                      self.chunked_admitted, self.resumes, len(self._done))
            self.step()
            if step_hook is not None:
                step_hook(self)
            if (self.decode_steps, self.prefill_admitted,
                    self.chunked_admitted, self.resumes,
                    len(self._done)) == before:
                stalled += 1
                if stalled >= self.cfg.stall_patience and not sched.idle:
                    raise EngineStalledError(
                        f"engine deadlocked: no progress for {stalled} "
                        f"consecutive steps with work outstanding",
                        sched.stuck_state())
            else:
                stalled = 0
        if not sched.idle:
            raise EngineStalledError(
                f"engine stopped after max_steps={max_steps} with work "
                f"outstanding", self.scheduler.stuck_state())
        out, self._done = self._done, []
        return out

    # -- invariants --------------------------------------------------------
    def check_invariants(self) -> bool:
        """Reconcile every piece of host bookkeeping against every other:
        scheduler slot partition, result-table coverage, block tables vs.
        per-slot page lists, and the allocator's refcounts/free list
        against the union of live block tables and prefix-cache
        references. Raises :class:`EngineInvariantError` naming the first
        mismatch; returns True when consistent. Pure host arithmetic — no
        device sync — so chaos tests call it after EVERY step."""
        sched = self.scheduler
        n = self.cfg.num_slots
        free, active = list(sched.free), list(sched.active_slots())
        if sorted(free + active) != list(range(n)):
            raise EngineInvariantError(
                f"slot partition broken: free={sorted(free)} "
                f"active={sorted(active)}")
        for slot in active:
            rid = sched.slots[slot].request.rid
            if rid not in self._results:
                raise EngineInvariantError(
                    f"active rid={rid} (slot {slot}) has no result entry")
        for item in sched.queue:
            req = item.request if isinstance(item, ResumeTicket) else item
            if req.rid not in self._results:
                raise EngineInvariantError(
                    f"queued rid={req.rid} has no result entry")
        pg, sentinel = self.cfg.page_size, self.alloc.num_pages
        want: Dict[int, int] = {}
        for slot in range(n):
            pages = self._slot_pages[slot]
            row = self._table[slot]
            if list(row[:len(pages)]) != pages or not np.all(
                    row[len(pages):] == sentinel):
                raise EngineInvariantError(
                    f"slot {slot} block table {row.tolist()} does not "
                    f"match page list {pages}")
            if slot in active:
                if len(pages) * pg < int(self._pos[slot]):
                    raise EngineInvariantError(
                        f"slot {slot} holds {len(pages)} pages "
                        f"({len(pages) * pg} tokens) but pos="
                        f"{int(self._pos[slot])}: cache rows unbacked")
            elif pages:
                raise EngineInvariantError(
                    f"parked slot {slot} still holds pages {pages}")
            for p in pages:
                want[p] = want.get(p, 0) + 1
        if self.prefix is not None:
            for p in self.prefix.pages():
                want[p] = want.get(p, 0) + 1
        refs = self.alloc.refs()
        if want != refs:
            diff = {p: (want.get(p, 0), refs.get(p, 0))
                    for p in set(want) | set(refs)
                    if want.get(p, 0) != refs.get(p, 0)}
            raise EngineInvariantError(
                f"page refcounts out of sync (page: want/have): {diff}")
        free_pages = self.alloc.free_pages()
        free_set = set(free_pages)
        if len(free_set) != len(free_pages):
            raise EngineInvariantError("free list holds duplicate pages")
        if free_set & set(refs):
            raise EngineInvariantError(
                f"pages both free and referenced: {free_set & set(refs)}")
        if len(free_set) + len(refs) != self.alloc.num_pages:
            orphans = set(range(self.alloc.num_pages)) - free_set - set(refs)
            raise EngineInvariantError(
                f"pages leaked (neither free nor referenced): {orphans}")
        return True

    # -- introspection -----------------------------------------------------
    def compile_counts(self) -> Dict[str, int]:
        """Compiled-program counts (prefill: one per (prompt bucket, batch
        bucket) pair seen; chunk: 1 when the trace has beyond-largest-bucket
        prompts or prefix hits; decode: 1). Flat across a post-warmup trace
        ⇔ no recompilation. The spill/restore gathers compile lazily at the
        first preemption (O(log max_pages) programs, bounded by the pow2
        padding) and are not tracked here."""
        return {"prefill": self._prefill._cache_size(),
                "chunk": self._chunk._cache_size(),
                "decode": self._decode._cache_size()}

    def lowered_text(self, program: str) -> str:
        """StableHLO text of the ``"decode"`` or ``"chunk"`` program at
        this engine's shapes, lowered from its current state without
        running it. Pallas kernels show up as ``tpu_custom_call`` ops with
        their ``kernel_name`` when lowered for TPU."""
        fn, args = {"decode": (self._decode, self._decode_args),
                    "chunk": (self._chunk, self._dummy_chunk_args)}[program]
        return fn.lower(*args()).as_text()

    def kv_cache_bytes(self) -> int:
        return cache_bytes(self.kv)

    def page_stats(self) -> Dict[str, int]:
        """Page-pool / prefix-reuse / preemption observability. Counters
        reset with :meth:`warmup`."""
        return {
            "num_pages": self.alloc.num_pages,
            "page_size": self.cfg.page_size,
            "pages_in_use": self.alloc.pages_in_use,
            "peak_pages_in_use": self.alloc.peak_in_use,
            "alloc_calls": self.alloc.alloc_calls,
            "alloc_failures": self.alloc.alloc_failures,
            "prefix_cached_pages": (self.prefix.cached_pages
                                    if self.prefix is not None else 0),
            "prefix_inserted_pages": (self.prefix.inserted_pages
                                      if self.prefix is not None else 0),
            "prefix_evicted_pages": (self.prefix.evicted_pages
                                     if self.prefix is not None else 0),
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "preemptions": self.preemptions,
            "resumes": self.resumes,
            "pages_spilled": self.pages_spilled,
        }

    def utilization(self) -> float:
        if self.decode_steps == 0:
            return 0.0
        return self.active_slot_steps / (self.decode_steps
                                         * self.cfg.num_slots)

    def queue_stats(self) -> Dict[str, Any]:
        """Backlog observability — a view over the registry's queue-depth
        gauge: queue depth sampled at each step boundary (peak / mean over
        ALL samples; the per-step trace is a ring of the most recent
        ``cfg.queue_trace_samples`` values with overwrites counted in
        ``dropped``) plus the ``try_submit`` load-shed count. Counters
        reset with :meth:`warmup`."""
        g = self._g_queue
        return {"peak": int(g.peak),
                "mean": g.mean,
                "samples": g.samples,
                "rejected": self.rejected,
                "trace": [int(v) for v in g.trace_values()],
                "dropped": g.trace_dropped}

    def metrics_snapshot(self) -> dict:
        """Full registry snapshot (counters/gauges/histograms) with the
        point-in-time state gauges refreshed. The canonical export for
        benches and ``serve.py --metrics-json`` — everything
        ``page_stats``/``queue_stats``/the legacy counter attributes show
        is derivable from it."""
        self._g_slots.set_value(self.scheduler.num_active)
        self._g_pages.set_value(self.alloc.pages_in_use)
        if self.prefix is not None:
            self._g_prefix_pages.set_value(self.prefix.cached_pages)
        return self.metrics.snapshot()


__all__ = ["Engine", "EngineConfig", "GenerationRequest", "GenerationResult",
           "batch_buckets"]
