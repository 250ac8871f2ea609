"""Serving cells: open-loop traffic through ``Engine.submit``/``Engine.step``.

Set-up (timed as ``setup_s``, from process start): the packed weights from
the seed in one jitted call, the engine, ``Engine.warmup`` over the
prompt lengths this run will send, and a pre-roll that fills the slots as
a steady state would. The window then offers the cell's requests at their
due times for ``--seconds``; nothing compiles in it. Each token's time is
the host clock when the ``Engine.step`` that made it returned (the step
waits for its one transfer from the device); a first token's time is the
engine's own stamp.

After the window: the device's peak memory is read, the program's state
is freed, and the plain reference (``reference.py`` over the cell's block
module, ``blocks/``) runs over a sample of
the finished requests, drawn from the seed, with the longest among them.
The compared number is the widest gap by which a served token's reference
logit lies below the reference's best at its position, as a share of the
largest reference logit there.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import sys
import time
import types
from typing import Dict, List, Tuple

import numpy as np

from bench.harness import traffic as T
from bench.harness import weights as W
from bench.harness.cli import Check, Outcome
from bench.harness.spans import Spans

PREROLL_RID = 1_000_000


@dataclasses.dataclass
class StepRecord:
    """One ``Engine.step`` as the harness saw it: the live context (tokens)
    of each request that got a decode token."""
    contexts: Tuple[int, ...]


@dataclasses.dataclass
class ServeContext:
    """What the per-layer readers of a serving cell read: the cell's block
    module (its model operations and attention layers) and sizes."""
    block: types.ModuleType
    dims: object
    engine_cfg: dict
    steps: List[StepRecord] = dataclasses.field(default_factory=list)
    trace: object = None               # trace_reduce.Reduction


def program_model(block, dims, arch: str):
    """The program's model: the repo's config of ``arch`` at the
    configuration's sizes, by the block's ``program_config``."""
    from repro.configs import get_config
    from repro.models import build_model
    return build_model(block.program_config(get_config(arch), dims),
                       remat=False)


def engine_config(settings: dict):
    import jax.numpy as jnp
    from repro.serving import EngineConfig
    kw = dict(settings)
    kw["prompt_buckets"] = tuple(kw.get("prompt_buckets", ()))
    kw["kv_dtype"] = getattr(jnp, kw.get("kv_dtype", "float32"))
    return EngineConfig(**kw)


def _request(r: T.Request):
    from repro.serving.scheduler import GenerationRequest
    return GenerationRequest(rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new)


class Tracer:
    """Starts the profiler at the first step boundary at or after
    ``start`` and stops it at the first one at or after ``stop``, after the
    device has finished, so the trace holds whole steps: those numbered
    [first, last) in the harness's records."""

    def __init__(self, enabled: bool, trace_dir: str, start: float,
                 stop: float):
        self.enabled, self.dir = enabled, trace_dir
        self.start_t, self.stop_t = start, stop
        self.on = False
        self.first = self.last = 0

    def at(self, now: float, n_steps: int, engine) -> None:
        if not self.enabled:
            return
        from bench import trace_reduce
        if not self.on and self.first == self.last == 0 and \
                self.start_t <= now < self.stop_t:
            if os.path.isdir(self.dir):
                shutil.rmtree(self.dir)
            trace_reduce.start(self.dir)
            self.on, self.first = True, n_steps
        elif self.on and now >= self.stop_t:
            import jax
            jax.block_until_ready(engine.kv)
            trace_reduce.stop()
            self.on, self.last = False, n_steps


class Tracker:
    """Token counts and times of every request the harness submitted,
    read from the results the engine fills in place."""

    def __init__(self):
        self.res: Dict[int, object] = {}
        self.seen: Dict[int, int] = {}
        self.last: Dict[int, float] = {}
        self.gaps: List[float] = []

    def add(self, rid: int, res) -> None:
        self.res[rid] = res
        self.seen[rid] = 0

    def after_step(self, t: float, window: tuple) -> tuple:
        """Account the tokens made by the step that returned at ``t``.
        Returns the live context of each request that got a decode
        token."""
        contexts = []
        for rid, res in self.res.items():
            n, before = len(res.tokens), self.seen[rid]
            if n == before:
                continue
            if before == 0:
                self.last[rid] = res.t_first_token
            if n > max(before, 1):                 # one decode token
                contexts.append(res.prompt_len + n - 1)
                if window[0] <= t <= window[1]:
                    self.gaps.append(t - self.last[rid])
                self.last[rid] = t
            self.seen[rid] = n
        return tuple(contexts)

    def drop_finished(self, rids) -> None:
        for rid in rids:
            self.res.pop(rid, None)


def run(cell, *, seed: int, seconds: float, trace: bool, started: float,
        root: str, verify=None) -> Outcome:
    """One run of a serving cell. ``verify(seed, block, dims, recipe,
    picked, prompts, spec) -> [Check]`` judges the sampled finished requests;
    by default :func:`served_token_checks`."""
    import jax
    from repro.obs import MetricsRegistry
    from repro.serving import Engine
    from bench import trace_reduce
    from bench.harness.cli import memory_peak_bytes

    cfg, mix = cell.config, cell.traffic
    block, dims = cell.block, cell.dims
    recipe = W.Recipe.from_config(cfg["weights"])
    ecfg = engine_config(cfg["engine"])
    spans = Spans(enabled=trace)

    # -- set-up --------------------------------------------------------------
    params = W.served_params(seed, block, dims, recipe)
    model = program_model(block, dims, cfg["arch"])
    engine = Engine(model, params, ecfg, registry=MetricsRegistry())
    del params
    window = T.open_loop(mix, seed, seconds, dims.vocab_size, ecfg.max_len)
    pre = T.preroll(mix, seed, mix["preroll_requests"], dims.vocab_size,
                    ecfg.max_len)
    engine.warmup([_request(r) for r in window + pre])
    tracker = Tracker()
    for i, r in enumerate(pre):
        req = _request(dataclasses.replace(r, rid=PREROLL_RID + i))
        engine.submit(req)
        tracker.add(req.rid, engine._results[req.rid])
    while engine.scheduler.queue:
        engine.step()
    jax.block_until_ready(engine.kv)
    tracker.after_step(time.perf_counter(), (math.inf, math.inf))
    done = {r.rid: r for r in engine._done}
    engine._done.clear()

    # -- window --------------------------------------------------------------
    t0 = time.perf_counter()
    setup_s = t0 - started
    t_end = t0 + seconds
    lo, span = mix.get("trace_window_s", [0.0, seconds])
    trace_dir = os.path.join(root, ".bench_trace", cell.name)
    tracer = Tracer(trace, trace_dir, t0 + lo, t0 + lo + span)
    steps: List[StepRecord] = []
    submitted, late = 0, []
    due_abs = [t0 + r.due_s for r in window]
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        tracer.at(now, len(steps), engine)
        while submitted < len(window) and due_abs[submitted] <= now:
            req = _request(window[submitted])
            with spans("submit"):
                engine.submit(req)
            tracker.add(req.rid, engine._results[req.rid])
            late.append(now - due_abs[submitted])
            submitted += 1
        if engine.scheduler.idle:
            nxt = due_abs[submitted] if submitted < len(window) else t_end
            with spans("idle"):
                time.sleep(max(0.0, min(nxt, t_end) - now))
            continue
        with spans("Engine.step"):
            engine.step()
        contexts = tracker.after_step(time.perf_counter(), (t0, t_end))
        for r in engine._done:
            done[r.rid] = r
        tracker.drop_finished([r.rid for r in engine._done])
        engine._done.clear()
        steps.append(StepRecord(contexts))
    tracer.at(math.inf, len(steps), engine)
    t_close = time.perf_counter()

    # -- end-to-end numbers ---------------------------------------------------
    gaps = tracker.gaps
    values = {
        "setup_s": setup_s,
        "tpot_ms": (sum(gaps) / len(gaps)) * 1e3 if gaps else math.inf,
    }
    failed = sum(1 for r in done.values() if r.status != "ok")
    finished = [r for r in done.values() if r.status == "ok"]
    print(f"[bench] window {t_close - t0:.3f}s: {len(window)} due, "
          f"{len(finished)} finished, {len(steps)} steps, {len(gaps)} "
          f"inter-token gaps; generator late by {max(late or [0]) * 1e3:.1f}"
          f" ms at most; set-up {setup_s:.2f}s; {values}",
          file=sys.stderr, flush=True)

    mem = memory_peak_bytes(cell.chips)
    ctx = ServeContext(block, dims, cfg["engine"])
    if trace:
        ctx.steps = steps[tracer.first:tracer.last]
        ctx.trace = trace_reduce.reduce_dir(trace_dir)

    # -- correct --------------------------------------------------------------
    prompts = {r.rid: r.prompt for r in window}
    prompts.update((PREROLL_RID + i, r.prompt) for i, r in enumerate(pre))
    del engine, model
    gc.collect()
    picked = sample(finished, seed, mix["check"]["served_tokens"])
    checks = (verify or served_token_checks)(seed, block, dims, recipe,
                                             picked, prompts, mix["check"])
    return Outcome(values, len(window) + len(pre), failed, checks, mem, ctx)


def served_token_checks(seed, block, dims, recipe, picked, prompts,
                        spec) -> List[Check]:
    worst, n = served_token_gap(seed, block, dims, recipe, picked, prompts)
    print(f"[bench] checked {len(picked)} finished requests, {n} served "
          f"tokens", file=sys.stderr, flush=True)
    return [Check("served_token_gap", worst, spec["served_token_gap"])]


def sample(finished, seed: int, budget: int) -> list:
    """The longest finished request, then others in a seeded order, while
    the served tokens stay within ``budget``."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.tokens), r.rid))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([seed, 2])
    picked, total = [longest], len(longest.tokens)
    for i in rng.permutation(len(rest)):
        r = rest[int(i)]
        if total + len(r.tokens) > budget:
            continue
        picked.append(r)
        total += len(r.tokens)
    return picked


def gaps(stats) -> np.ndarray:
    """Per position: (reference best - reference logit of the token read)
    over the largest |reference logit| there."""
    return (np.asarray(stats["best"], np.float64)
            - np.asarray(stats["got"], np.float64)) \
        / np.asarray(stats["absmax"], np.float64)


def sequences(picked, prompts):
    """Per request: prompt + served tokens but the last, and the position
    whose logits chose the first served token."""
    seqs, starts = [], []
    for r in picked:
        p = prompts[r.rid]
        seqs.append(np.concatenate([p, np.asarray(r.tokens[:-1], np.int32)]))
        starts.append(len(p) - 1)
    return seqs, starts


def served_token_gap(seed, block, dims, recipe, picked, prompts) -> tuple:
    """(widest gap over every served token of ``picked``, tokens read);
    a run with nothing finished to read reads as infinitely far."""
    if not picked:
        return math.inf, 0
    from bench.harness import reference
    seqs, starts = sequences(picked, prompts)
    stats = reference.token_stats(seed, block, dims, recipe, seqs, starts,
                                  [np.asarray(r.tokens) for r in picked])
    worst = max(float(gaps(st).max()) for st in stats)
    return worst, sum(len(r.tokens) for r in picked)
