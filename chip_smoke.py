"""Chip smoke: the main path once, at llama32-1b width, on one TPU.

    python3 chip_smoke.py            # from the repository root

Phases, each printing its wall and compile seconds:

  (a) device   — JAX's first device must be a TPU, or exit non-zero before
                 any work (there is no CPU path);
  (b) compress — AWP INT4 (group 128) over all 16 blocks through
                 ``compress_model`` on the batched engine, fused ``awp_pgd``
                 kernel; random weights from ``--seed``, Zipf-Markov
                 calibration data;
  (c) precision— one layer's loss and compression again under
                 ``jax.default_matmul_precision("highest")``, and the fused
                 PGD step against the float32 reference;
  (d) ckpt     — ``save_packed_checkpoint`` → ``load_packed_checkpoint``;
                 every quantized leaf must come back as a nibble-packed
                 ``QTensor`` (the ``dequant_matmul`` kernel path);
  (e) serve    — continuous batching on the packed weights, slot/dense KV
                 and paged/INT8 KV: every request ``ok``, no recompilation
                 after warmup, the engine's decode and chunk programs hold
                 the expected Pallas kernels, fused-decode logits close to
                 the reference cache read, dense-KV greedy tokens
                 bit-identical to the static path (reference attention),
                 kernel-path prefill logits close to the reference dequant
                 path, and what the serving precision pin costs per step.

Compile seconds are the XLA backend compile time JAX reports (persistent
cache reads included), so a warm ``.jax_cache`` shows up as a lower number.
The last line of standard output is the JSON result; any failed check
exits non-zero without printing it.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

ARCH = "llama32-1b"
CALIB_BATCHES, CALIB_BATCH, CALIB_SEQ = 4, 8, 128
QUANT_BITS, QUANT_GROUP = 4, 128
PRECISION_LAYER = ("blocks", "attn", "wq")     # checked in block 0
LOSS_TOL = 1e-2            # relative loss gaps, default vs "highest"
# fused PGD step vs the f32 reference: the kernel runs one bf16 MXU pass at
# default precision; the bound is bf16's unit roundoff, ~1e4 x an f32 error
STEP_TOL = 2.0 ** -8
N_REQUESTS, SLOTS, MAX_PROMPT, MAX_NEW = 16, 8, 64, 32
MAX_LEN = MAX_PROMPT + MAX_NEW
PROMPT_BUCKETS = (32,)     # one bucket: 4 batch buckets + chunk + decode
PAGE_SIZE = 16             # divides MAX_LEN
LOGIT_TOL = 2e-2           # max |kernel - reference| / max |reference|
# fused vs reference decode logits, both at f32: reordered f32 sums stay
# near 1e-6, one bf16 pass is near 1e-2, a misread tile is O(1)
DECODE_TOL = 1e-3
TIMED_CALLS = 20           # per variant, for the precision pin's cost

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Running sum of the backend compile seconds JAX reports."""

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.total += duration


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, times: dict):
    t0, c0 = time.perf_counter(), clock.total
    print(f"[smoke] ({name}) ...", flush=True)
    yield
    wall, comp = time.perf_counter() - t0, clock.total - c0
    times[name] = (wall, comp)
    print(f"[smoke] ({name}) wall {wall:.1f}s compile {comp:.1f}s",
          flush=True)


def device_check():
    """Phase (a): the TPU, as JAX reports it — or exit before any work."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[smoke] (a) no TPU: JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        raise SystemExit(2)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def peak_gb(jax) -> float:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


def native_kernels(lowered_text: str, kernels, what: str) -> None:
    """The lowered program holds each named Pallas kernel as a Mosaic
    custom call (compiled natively, not interpreted)."""
    missing = [k for k in kernels
               if f'kernel_name = "{k}"' not in lowered_text]
    check(not missing, f"{what}: no native {missing} in the lowered program")
    print(f"[smoke]   {what}: native {', '.join(kernels)}")


def first_block(jax, params):
    """Block 0 of the original weights (embedding shared), kept for phase
    (c) while compression replaces the full tree."""
    return {**params, "blocks": jax.tree.map(lambda x: x[:1],
                                             params["blocks"])}


def compress(jax, jnp, cfg, model, state: dict, seed: int):
    """Phase (b). Takes the weights out of ``state`` so that compress_model
    holds the only reference to the original tree and can free it as the
    compressed leaves replace it."""
    from repro.core import awp, batched
    from repro.core.compress import compress_model
    from repro.core.specs import Policy, QuantSpec
    from repro.data import DataConfig, calibration_batches
    from repro.kernels import ops
    from repro.obs import MetricsRegistry

    check(not ops._interpret() and awp.PGDConfig(use_pallas=True)
          .fused_step(), "awp_pgd would not run natively")
    d, f = cfg.d_model, cfg.d_ff
    sds = jax.ShapeDtypeStruct
    native_kernels(batched.quantize_batched.lower(
        sds((2, f, d), jnp.float32), sds((2, d, d), jnp.float32),
        QUANT_BITS, group_size=QUANT_GROUP).as_text(), ["awp_pgd"],
        "quantize_batched")

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=CALIB_SEQ,
                    global_batch=CALIB_BATCH, seed=seed)
    calib = [{"tokens": jnp.asarray(t)}
             for t, _ in calibration_batches(dc, CALIB_BATCHES)]
    spec = QuantSpec(method="awp_quant", bits=QUANT_BITS,
                     group_size=QUANT_GROUP)
    t0 = time.perf_counter()
    cp, report = compress_model(model, state.pop("params"), calib,
                                Policy(default=spec),
                                metrics=MetricsRegistry())
    jax.block_until_ready(cp)
    wall = time.perf_counter() - t0
    nb = model.num_blocks()
    for b in range(nb):
        rows = [r for r in report.layers if r.block == b]
        secs = sum(r.seconds for r in rows)
        cells = " ".join(
            f"{r.name}={r.loss_after:.4f}/"
            f"{report.artifacts[r.qualname].result.iters}it" for r in rows)
        print(f"[smoke]   block {b:2d} {secs:6.2f}s  {cells}")
    check(len(report.layers) == 7 * nb,
          f"{len(report.layers)} layers compressed, want {7 * nb}")
    losses = [r.loss_after for r in report.layers]
    check(all(0.0 <= x < 1.0 for x in losses),
          f"normalized losses out of range: {min(losses)}..{max(losses)}")
    print(f"[smoke]   {nb} blocks in {wall:.1f}s -> {wall / nb:.2f} s/block "
          f"(block 0 includes its compiles); mean loss "
          f"{report.mean_loss():.4f}; peak HBM {peak_gb(jax):.2f} GB")
    return cp, report, calib, spec


def precision(jax, jnp, model, params, cp, calib, spec):
    """Phase (c): does TPU default matmul precision (one bf16 pass for f32
    operands) move the algorithm's results?"""
    from repro.core import awp, calibration as calib_mod
    from repro.core.compress import compress_layer, get_linear
    from repro.kernels import ops, ref

    w = get_linear(params, PRECISION_LAYER, 0)
    theta = get_linear(cp, PRECISION_LAYER, 0)

    def stats():
        st = calib_mod.init(model.cfg.d_model)
        for b in calib:
            h = model.embed(params, b)
            _, caps = model.block_apply_one(params, 0, h, capture=True)
            st = calib_mod.update(st, caps["attn_in"])
        return st

    st_d = stats()
    c_d = calib_mod.covariance(st_d, damp=spec.damp)
    loss_d = float(awp.activation_loss(w, theta, c_d))
    with jax.default_matmul_precision("highest"):
        st_h = stats()
        c_h = calib_mod.covariance(st_h, damp=spec.damp)
        loss_h = float(awp.activation_loss(w, theta, c_h))
        theta_h = compress_layer(w, st_h, spec).theta
        loss_hh = float(awp.activation_loss(w, theta_h, c_h))
        # Θ = 0 leaves Z = ηWC: the kernel's contraction alone, with no
        # rounding of Θ + ηR in the way
        eta = 1.5 / jnp.linalg.norm(c_h)
        zeros = jnp.zeros_like(w)
        z_ref = ref.awp_pgd_step(w, zeros, c_h, eta)
        u_ref = ref.awp_pgd_step(w, theta, c_h, eta)
    z_ker = ops.awp_pgd_step(w, zeros, c_h, eta)
    u_ker = ops.awp_pgd_step(w, theta, c_h, eta)
    eval_gap = abs(loss_d - loss_h) / loss_h
    comp_gap = (loss_h - loss_hh) / loss_hh
    step_err = float(jnp.linalg.norm(z_ker - z_ref) / jnp.linalg.norm(z_ref))
    update_err = float(jnp.linalg.norm(u_ker - u_ref)
                       / jnp.linalg.norm(u_ref - theta))
    name = "blocks.0." + ".".join(PRECISION_LAYER[1:])
    print(f"[smoke]   {name}: loss default {loss_d:.6f} vs highest "
          f"{loss_h:.6f} (gap {eval_gap:.2e}); compressed under highest "
          f"{loss_hh:.6f} (gap {comp_gap:.2e}); fused step ηWC vs f32 "
          f"reference {step_err:.2e} relative (a step from the compressed "
          f"Θ: {update_err:.2e} of the update); tolerances "
          f"{LOSS_TOL:.0e} (losses), {STEP_TOL:.1e} (step)")
    check(eval_gap <= LOSS_TOL, "loss moves with matmul precision")
    check(abs(comp_gap) <= LOSS_TOL,
          "compression result moves with matmul precision")
    check(step_err <= STEP_TOL,
          "fused PGD step is further from f32 than one bf16 pass")


def checkpoint_roundtrip(jax, model, cp, report, seed: int, workdir: str):
    """Phase (d)."""
    from repro.checkpoint import load_packed_checkpoint, save_packed_checkpoint
    from repro.launch.serve import qtensor_leaves

    t0 = time.perf_counter()
    path = save_packed_checkpoint(workdir, 0, cp, report)
    t_save = time.perf_counter() - t0
    target = jax.eval_shape(model.init, jax.random.PRNGKey(seed))
    t0 = time.perf_counter()
    params, qts, _ = load_packed_checkpoint(path, target)
    jax.block_until_ready(params)
    t_load = time.perf_counter() - t0
    leaves = qtensor_leaves(params)
    check(len(leaves) == 7, f"{len(leaves)} packed leaves, want 7")
    for qt in leaves:
        nibble = qt.bits == 4 and qt.packed.shape[-1] * 2 == qt.shape[1]
        check(nibble, f"QTensor {qt.shape} bits={qt.bits} would take the "
                      f"reference matmul, not dequant_matmul")
    size = sum(os.path.getsize(os.path.join(path, n))
               for n in os.listdir(path))
    print(f"[smoke]   {len(qts)} layers -> {len(leaves)} stacked INT4 "
          f"QTensor leaves; save {t_save:.1f}s load {t_load:.1f}s, "
          f"{size / 1e9:.2f} GB on disk")
    return params


def serve(jax, jnp, cfg, model, params, seed: int, **ecfg_kw):
    """One continuous-batching run; returns (engine, trace, results)."""
    from repro.launch.serve import build_trace
    from repro.serving import Engine, EngineConfig

    reqs = build_trace(cfg, num_requests=N_REQUESTS, max_prompt=MAX_PROMPT,
                       max_new=MAX_NEW, seed=seed)
    engine = Engine(model, params, EngineConfig(
        num_slots=SLOTS, max_len=MAX_LEN, prompt_buckets=PROMPT_BUCKETS,
        kv_dtype=jnp.float32, page_size=PAGE_SIZE, **ecfg_kw))
    t0 = time.perf_counter()
    compiled = engine.warmup(reqs)
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    results = engine.run()
    wall = time.perf_counter() - t0
    after = engine.compile_counts()
    statuses = {}
    for r in results:
        statuses[r.status] = statuses.get(r.status, 0) + 1
    n_tok = sum(len(r.tokens) for r in results)
    print(f"[smoke]   {ecfg_kw}: warmup {t_warm:.1f}s, {len(results)} "
          f"requests {n_tok} tokens in {wall:.2f}s, statuses {statuses}, "
          f"programs {after} (after warmup {compiled}), kv "
          f"{engine.kv_cache_bytes() / 1e6:.1f} MB")
    bad = [r for r in results if not r.ok]
    check(not bad, "not ok: " + "; ".join(
        f"rid {r.rid} {r.status} {r.error}" for r in bad))
    check(len(results) == len(reqs), f"{len(results)}/{len(reqs)} results")
    check(after == compiled, f"recompiled after warmup: {compiled} -> "
                             f"{after}")
    print("[smoke]   no program recompiled after warmup")
    kv_read = ["kv_dequant"] if engine.cfg.kv_quantized else []
    native_kernels(engine.lowered_text("decode"),
                   ["flash_decode", "dequant_matmul"], "engine decode")
    native_kernels(engine.lowered_text("chunk"), ["dequant_matmul"] + kv_read,
                   "engine chunk")
    return engine, reqs, results


def decode_parity(jax, jnp, model, engine) -> None:
    """The fused flash-decode read against the reference dequant-then-attend
    read: one decode step on the cache the engine's run left behind, 8 rows
    at positions spread over [0, max_len), so rows end in different tiles
    (pages) and skip different dead ones."""
    from repro.serving.engine import step_jit

    vocab = model.cfg.vocab_size
    s = engine.cfg.num_slots
    pos = jnp.linspace(0, MAX_LEN - 1, s).astype(jnp.int32)
    tok = jnp.arange(1, s + 1, dtype=jnp.int32)[:, None]
    table = ()
    if engine.alloc is not None:            # paged: slot i owns a page run
        n = engine.pages_per_slot
        table = (jnp.arange(s * n, dtype=jnp.int32).reshape(s, n)
                 % engine.alloc.num_pages,)

    def logits_fn(fused: bool):
        m = dataclasses.replace(model, use_fused_decode=fused)

        def f(params, kv, *tbl):
            cache = {"k": kv["k"], "v": kv["v"], "pos": pos}
            if tbl:
                cache["table"] = tbl[0]
            return m.decode_step(params, tok, cache)[0][:, 0, :vocab]
        return step_jit(f)

    fused, ref = logits_fn(True), logits_fn(False)
    native_kernels(fused.lower(engine.params, engine.kv, *table).as_text(),
                   ["flash_decode"], "fused decode step")
    k = fused(engine.params, engine.kv, *table)
    r = ref(engine.params, engine.kv, *table)
    rel = float(jnp.max(jnp.abs(k - r)) / jnp.max(jnp.abs(r)))
    same = int(jnp.sum(jnp.argmax(k, -1) == jnp.argmax(r, -1)))
    print(f"[smoke]   decode logits fused vs reference read at positions "
          f"{pos.tolist()}: max rel diff {rel:.2e} (tolerance "
          f"{DECODE_TOL:.0e}), argmax equal {same}/{s}")
    check(rel <= DECODE_TOL, "fused decode logits far from the reference")


def verify_static(model, params, reqs, results) -> None:
    """Each request alone on the static path (batch 1, exact prompt, the
    reference cache read): the engine's tokens, batched and read through
    the fused decode kernel, must match bit for bit."""
    from repro.launch.serve import make_step_fns, static_greedy_reference
    check(not model.use_fused_decode, "static oracle must read unfused")
    step_fns = make_step_fns(model)
    by_rid = {r.rid: r.tokens for r in results}
    bad = []
    for q in reqs:
        ref = static_greedy_reference(model, params, q, MAX_LEN, step_fns)
        got = by_rid[q.rid]
        if got != ref:
            at = next(i for i, (a, b) in enumerate(zip(got, ref)) if a != b)
            bad.append(q.rid)
            print(f"[smoke]   rid {q.rid} (prompt {q.prompt_len}) first "
                  f"differs at token {at}: {got[at]} vs static {ref[at]}")
    print(f"[smoke]   dense-KV greedy vs static path: "
          f"{len(reqs) - len(bad)}/{len(reqs)} bit-identical")
    check(not bad, f"engine tokens differ from the static path: rids {bad}")


def logit_parity(jax, jnp, cfg, model, params, reqs) -> None:
    """Kernel-path prefill logits against the reference dequant path, each
    mode traced in its own jit (the mode is read at trace time), both
    built like the serving programs."""
    from repro.quant import matmul_impl
    from repro.serving.engine import step_jit

    def prefill_logits(p, tokens):
        cache = model.init_cache(1, tokens.shape[1], jnp.float32)
        return model.prefill(p, {"tokens": tokens}, cache)[0]

    longest = max(reqs, key=lambda r: r.prompt_len)
    tokens = jnp.asarray(longest.prompt[None, :PROMPT_BUCKETS[-1]])
    with matmul_impl("kernel"):
        kern = step_jit(prefill_logits)
        native_kernels(kern.lower(params, tokens).as_text(),
                       ["dequant_matmul"], "packed prefill")
        k = kern(params, tokens)
    with matmul_impl("reference"):
        r = step_jit(lambda p, t: prefill_logits(p, t))(params, tokens)
    v = cfg.vocab_size
    k, r = k[..., :v], r[..., :v]
    rel = float(jnp.max(jnp.abs(k - r)) / jnp.max(jnp.abs(r)))
    same = bool(jnp.all(jnp.argmax(k, -1) == jnp.argmax(r, -1)))
    print(f"[smoke]   prefill logits kernel vs reference: max rel diff "
          f"{rel:.2e} (tolerance {LOGIT_TOL:.0e}), argmax equal {same}")
    check(rel <= LOGIT_TOL, "kernel-path logits far from the reference")


def pin_cost(jax, jnp, model, params) -> None:
    """Device time of the packed model's decode step (8 rows over a
    max_len cache, fused read) and prefill (8 x 32 tokens), built with
    ``step_jit`` (f32 dots) and with plain ``jax.jit`` (TPU default
    precision); the variants alternate call by call, median of
    TIMED_CALLS each."""
    from repro.serving.engine import step_jit

    m = dataclasses.replace(model, use_fused_decode=True)
    b, w = SLOTS, PROMPT_BUCKETS[-1]
    tokens = jnp.ones((b, w), jnp.int32)

    def prefill(p, toks):
        return m.prefill(p, {"tokens": toks},
                         m.init_cache(b, MAX_LEN, jnp.float32))

    def decode(p, tok, cache):
        return m.decode_step(p, tok, cache)

    variants = {"highest": step_jit, "default": jax.jit}
    progs = {k: (jit(prefill), jit(decode, donate_argnums=2))
             for k, jit in variants.items()}
    state = {}
    for k, (pf, _) in progs.items():
        logits, cache = pf(params, tokens)
        state[k] = (jnp.argmax(logits[:, -1:], -1).astype(jnp.int32), cache)
    times = {(k, what): [] for k in progs for what in ("prefill", "decode")}
    for i in range(TIMED_CALLS + 1):        # call 0 compiles decode
        for k, (pf, dc) in progs.items():
            t0 = time.perf_counter()
            jax.block_until_ready(pf(params, tokens))
            t1 = time.perf_counter()
            tok, cache = state[k]
            logits, cache = dc(params, tok, cache)
            jax.block_until_ready(logits)
            t2 = time.perf_counter()
            state[k] = (tok, cache)
            if i:
                times[k, "prefill"].append(t1 - t0)
                times[k, "decode"].append(t2 - t1)
    med = {key: sorted(v)[len(v) // 2] * 1e3 for key, v in times.items()}
    print(f"[smoke]   precision pin cost, median of {TIMED_CALLS}: decode "
          f"step {med['highest', 'decode']:.3f} ms at highest vs "
          f"{med['default', 'decode']:.3f} ms at default; prefill "
          f"{b}x{w} {med['highest', 'prefill']:.3f} ms vs "
          f"{med['default', 'prefill']:.3f} ms")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    times: dict = {}

    device = device_check()                                  # phase (a)
    sys.path.insert(0, os.path.join(HERE, "src"))
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model

    cache_dir = enable_compile_cache()
    clock = CompileClock(jax)
    print(f"[smoke] (a) device {device}, compile cache {cache_dir}")

    cfg = get_config(ARCH)
    model = build_model(cfg, remat=False)
    with phase("b compress", clock, times):
        state = {"params": model.init(jax.random.PRNGKey(args.seed))}
        block0 = first_block(jax, state["params"])
        cp, report, calib, spec = compress(jax, jnp, cfg, model, state,
                                           args.seed)
    with phase("c precision", clock, times):
        precision(jax, jnp, model, block0, cp, calib, spec)
    del block0
    with phase("d checkpoint", clock, times), \
            tempfile.TemporaryDirectory() as workdir:
        packed = checkpoint_roundtrip(jax, model, cp, report, args.seed,
                                      workdir)
    del cp, report
    with phase("e serve slot/dense", clock, times):
        engine, reqs, results = serve(jax, jnp, cfg, model, packed,
                                      args.seed, kv_layout="slots",
                                      kv_quantized=False)
        decode_parity(jax, jnp, model, engine)
        del engine
        verify_static(model, packed, reqs, results)
        logit_parity(jax, jnp, cfg, model, packed, reqs)
        pin_cost(jax, jnp, model, packed)
    with phase("e serve paged/int8", clock, times):
        engine, _, _ = serve(jax, jnp, cfg, model, packed, args.seed,
                             kv_layout="paged", kv_quantized=True)
        decode_parity(jax, jnp, model, engine)
        del engine

    total_wall = sum(w for w, _ in times.values())
    print(f"[smoke] total wall {total_wall:.1f}s compile "
          f"{clock.total:.1f}s, peak HBM {peak_gb(jax):.2f} GB")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
