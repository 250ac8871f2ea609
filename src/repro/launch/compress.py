"""Compression launcher — the paper's pipeline as a CLI.

  PYTHONPATH=src python -m repro.launch.compress --arch llama2-7b --tiny \
      --method awp_prune --ratio 0.6 --ckpt results/train_ckpt

Per-layer policies come from ``--policy`` (inline JSON or @file), e.g.

  --policy '{"rules": [["blocks.0.*", null],
                       ["*.attn.*", {"kind": "QuantSpec", "bits": 8}],
                       ["*.mlp.*",  {"kind": "QuantSpec", "bits": 4}]]}'

Loads a trained checkpoint (or compresses random init if absent), runs the
layer-wise compression through the method registry (shape-bucketed batched
engine by default; ``--engine sequential`` for the reference driver), reports
per-layer reconstruction losses + perplexity before/after, and saves the
compressed checkpoint — packed QTensor codes included with ``--save-packed``.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.checkpoint import (CheckpointManager, save_checkpoint,
                              save_packed_checkpoint)
from repro.configs import get_config, get_tiny_config
from repro.core import metrics, registry
from repro.core.compress import CompressionConfig, compress_model
from repro.core.specs import Policy
from repro.data import DataConfig, ZipfMarkov, calibration_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.obs import MetricsRegistry


def build_policy(args) -> "Policy | CompressionConfig":
    if args.policy:
        text = args.policy
        if text.startswith("@"):
            with open(text[1:]) as f:
                text = f.read()
        return Policy.from_dict(json.loads(text))
    return CompressionConfig(method=args.method, ratio=args.ratio,
                             bits=args.bits, group_size=args.group_size,
                             skip=tuple(args.skip))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--method", default="awp_prune",
                    choices=list(registry.available()))
    ap.add_argument("--ratio", type=float, default=0.5)
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--group-size", type=int, default=128)
    ap.add_argument("--skip", nargs="*", default=(),
                    help="layer-name patterns to leave dense")
    ap.add_argument("--policy", default="",
                    help="per-layer policy as JSON (or @file.json); "
                         "overrides --method/--ratio/--bits")
    ap.add_argument("--calib-batches", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt", default="results/train_ckpt")
    ap.add_argument("--out", default="results/compressed_ckpt")
    ap.add_argument("--save-packed", action="store_true",
                    help="store quantized layers as packed QTensor codes")
    ap.add_argument("--engine", default="batched",
                    choices=("batched", "sequential"),
                    help="shape-bucketed batched engine (default) or the "
                         "layer-at-a-time reference driver")
    ap.add_argument("--metrics-json", default="",
                    help="write the compression telemetry snapshot as JSON "
                         "here (plus Prometheus text exposition at "
                         "PATH.prom)")
    ap.add_argument("--profile-dir", default="",
                    help="directory for jax.profiler traces")
    ap.add_argument("--profile-block", type=int, default=-1,
                    help="block index to wrap in a jax.profiler trace "
                         "window (needs --profile-dir; -1 -> off)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_tiny_config(args.arch) if args.tiny else get_config(args.arch)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    mgr = CheckpointManager(args.ckpt)
    restored, step = mgr.restore_latest({"params": params})
    if restored is not None:
        params = restored["params"]
        print(f"[compress] loaded checkpoint step {step}")
    else:
        print("[compress] no checkpoint found — compressing random init "
              "(train first with repro.launch.train for meaningful numbers)")

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    global_batch=8)
    calib = [{"tokens": jnp.asarray(t), "labels": jnp.asarray(l)}
             for t, l in calibration_batches(dc, args.calib_batches)]
    gen = ZipfMarkov(dc)
    eval_batches = [gen.batch(9000 + i) for i in range(4)]

    def ppl(p):
        def loss_fn(p, t, l):
            _, m = jax.jit(model.loss)(p, {"tokens": t, "labels": l})
            return m["sum_nll"], m["tokens"]
        return metrics.perplexity(loss_fn, p, [
            (jnp.asarray(t), jnp.asarray(l)) for t, l in eval_batches])

    before = ppl(params)
    policy = build_policy(args)
    reg = MetricsRegistry()
    cp, report = compress_model(model, params, calib, policy, verbose=True,
                                engine=args.engine, metrics=reg,
                                profile_dir=args.profile_dir,
                                profile_block=args.profile_block)
    after = ppl(cp)
    print("[compress] " + report.summary().replace("\n", "\n[compress] "))
    if args.metrics_json:
        reg.dump_json(args.metrics_json, meta={
            "source": "compress", "arch": args.arch, "engine": args.engine,
            "method": args.method})
        with open(args.metrics_json + ".prom", "w") as f:
            f.write(reg.to_prometheus())
        print(f"[compress] metrics snapshot -> {args.metrics_json} "
              f"(+ {args.metrics_json}.prom)")
    print(f"[compress] perplexity {before:.3f} -> {after:.3f}")
    if args.save_packed and report.packed_layers():
        path = save_packed_checkpoint(args.out, 0, cp, report)
        print(f"[compress] wrote packed checkpoint {path} "
              f"(serve with --packed)")
    else:
        if args.save_packed:
            print("[compress] WARNING: no quantized artifacts to pack "
                  "(pruning-only policy?) — writing a dense checkpoint; "
                  "serve it WITHOUT --packed")
        save_checkpoint(args.out, 0, {"params": cp})
        print(f"[compress] wrote {args.out}")


if __name__ == "__main__":
    main()
