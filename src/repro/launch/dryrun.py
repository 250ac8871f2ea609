"""Multi-pod dry-run: AOT lower + compile every (arch × shape × mesh) cell.

Two lowerings per cell (see EXPERIMENTS.md §Dry-run "methodology"):

1. PRODUCTION lowering — the real scanned/remat program at full depth.
   Proves the cell compiles and fits: memory_analysis() is recorded.
2. COST lowerings — XLA's HloCostAnalysis counts a while-loop body ONCE
   (verified empirically), so scanned programs under-report FLOPs/bytes/
   collectives by ~num_layers ×. We therefore lower reduced-depth UNROLLED
   variants (every repeat-scan a python loop, chunk scans single-iteration)
   at 2-3 depths and reconstruct full-depth costs by exact linear fit
   f(L) = fixed + L·per_layer (+ ceil(L/p)·per_shared for the hybrid).

Everything is ShapeDtypeStruct — no allocation. The 512 devices are
virtual CPU devices: ``main()`` pins the CPU platform and sets
``XLA_FLAGS`` before JAX starts (never at import), so neither this process
nor the per-cell children it starts ever take an attached accelerator.

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--compress] [--timeout N]
"""
import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
import traceback


RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _out_path(mesh_name, arch, shape):
    d = os.path.abspath(os.path.join(RESULTS_DIR, mesh_name))
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{arch}__{shape}.json")


# ---------------------------------------------------------------------------
# lowering builders
# ---------------------------------------------------------------------------

def _build_lowered(cfg, mesh, shape, kind, *, unroll: bool, n_micro: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import build_model, batch_specs
    from repro.optim import OptimizerConfig
    from repro.sharding import (rules_for_cell, tree_shardings,
                                opt_logical_axes)
    from repro.training.train_loop import TrainConfig, make_train_step

    rules = rules_for_cell(mesh, cfg.family, kind,
                           global_batch=shape.global_batch)
    model = build_model(cfg, rules, param_dtype=jnp.bfloat16, remat=True)
    model.unroll = unroll
    model.attn_p_dtype = jnp.bfloat16   # TPU-flash convention (§Perf)
    if unroll:
        model.attn_chunk = max(shape.seq_len, 1024)
        model.logit_chunk = shape.seq_len
        if hasattr(model, "scan_chunk"):
            model.scan_chunk = shape.seq_len

    param_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_logical = model.param_logical_axes()
    p_shardings = tree_shardings(rules, p_logical, param_sds)
    n_active = cfg.active_param_count()

    if kind == "train":
        import jax.numpy as _jnp
        n_param = cfg.param_count()
        opt_name = "adafactor" if n_param > 20e9 else "adamw"
        # each microbatch must still span every batch shard
        batch_shards = rules.axis_size(rules.batch_axes)
        n_micro = max(1, min(n_micro, shape.global_batch // batch_shards))
        accum = _jnp.bfloat16 if (n_param > 100e9 and n_micro > 1) else _jnp.float32
        tcfg = TrainConfig(optimizer=OptimizerConfig(name=opt_name),
                           microbatches=n_micro, accum_dtype=accum,
                           unroll_accum=unroll)  # cost fit: count per-micro
                                                 # collectives (FSDP regathers)
        step_fn, opt_init = make_train_step(model, tcfg)
        opt_sds = jax.eval_shape(opt_init, param_sds)
        o_logical = opt_logical_axes(opt_name, p_logical, param_sds)
        o_shardings = tree_shardings(rules, o_logical, opt_sds)
        state_sds = {"params": param_sds, "opt": opt_sds,
                     "step": jax.ShapeDtypeStruct((), jnp.int32)}
        state_sh = {"params": p_shardings, "opt": o_shardings,
                    "step": NamedSharding(mesh, P())}
        b_sds = batch_specs(cfg, shape.global_batch, shape.seq_len)
        b_sh = {k: NamedSharding(mesh, rules.spec(("batch",) + (None,) * (len(v.shape) - 1),
                                                  v.shape))
                for k, v in b_sds.items()}
        with jax.set_mesh(mesh):
            lowered = jax.jit(step_fn, in_shardings=(state_sh, b_sh),
                              donate_argnums=0).lower(state_sds, b_sds)
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
        return lowered, model_flops

    cache_sds = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                 jnp.bfloat16))
    c_shardings = tree_shardings(rules, model.cache_logical_axes(), cache_sds)
    if kind == "prefill":
        b_sds = batch_specs(cfg, shape.global_batch, shape.seq_len)
        b_sds.pop("labels", None)
        b_sh = {k: NamedSharding(mesh, rules.spec(("batch",) + (None,) * (len(v.shape) - 1),
                                                  v.shape))
                for k, v in b_sds.items()}
        def serve_step(params, batch, cache):
            return model.prefill(params, batch, cache)
        args_sds = (param_sds, b_sds, cache_sds)
        args_sh = (p_shardings, b_sh, c_shardings)
        tokens = shape.global_batch * shape.seq_len
    else:  # decode: one new token against a seq_len-deep cache
        tok_sds = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        tok_sh = NamedSharding(mesh, rules.spec(("batch", None), tok_sds.shape))
        def serve_step(params, tokens_, cache):
            return model.decode_step(params, tokens_, cache)
        args_sds = (param_sds, tok_sds, cache_sds)
        args_sh = (p_shardings, tok_sh, c_shardings)
        tokens = shape.global_batch
    with jax.set_mesh(mesh):
        lowered = jax.jit(serve_step, in_shardings=args_sh,
                          donate_argnums=2).lower(*args_sds)
    return lowered, 2.0 * n_active * tokens


def _costs_of(compiled, hlo=None):
    from repro.analysis import roofline as rl
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    text = hlo if hlo is not None else compiled.as_text()
    coll = rl.collective_bytes(text)
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "collectives": coll,
            "collective_total": float(sum(coll.values()))}


def _fit_costs(cfg, mesh, shape, kind, n_micro):
    """Reduced-depth unrolled lowerings → exact linear reconstruction."""
    p = cfg.attn_every if cfg.family == "hybrid" else 0
    if p:
        depths = [p, p + 2, 2 * p]        # solves (fixed, per_layer, per_shared)
    else:
        depths = [1, 2]
    samples = []
    for L in depths:
        sub = dataclasses.replace(cfg, num_layers=L)
        lowered, _ = _build_lowered(sub, mesh, shape, kind,
                                    unroll=True, n_micro=n_micro)
        samples.append(_costs_of(lowered.compile()))

    import numpy as np
    def feats(L):
        row = [1.0, float(L)]
        if p:
            row.append(float(-(-L // p)))
        return row
    A = np.array([feats(L) for L in depths])

    def predict(vals):
        coef, *_ = np.linalg.lstsq(A, np.array(vals), rcond=None)
        # guard: the full model can never cost less than the deepest sample
        # (negative per-layer slopes = XLA hoisting artifacts at tiny L)
        return float(max(np.dot(feats(cfg.num_layers), coef), max(vals), 0.0))

    out = {"flops": predict([s["flops"] for s in samples]),
           "bytes": predict([s["bytes"] for s in samples]),
           "collective_total": predict(
               [s["collective_total"] for s in samples])}
    out["collectives"] = {
        k: predict([s["collectives"][k] for s in samples])
        for k in samples[0]["collectives"]}
    out["fit_depths"] = depths
    out["fit_samples"] = samples
    return out


def _mem_dict(mem) -> dict:
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    if "argument_size_in_bytes" in out and "temp_size_in_bytes" in out:
        out["total_per_device_bytes"] = (out["argument_size_in_bytes"]
                                         + out["output_size_in_bytes"]
                                         + out["temp_size_in_bytes"]
                                         - out.get("alias_size_in_bytes", 0))
    return out


# ---------------------------------------------------------------------------
# one cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_name: str) -> dict:
    import jax
    from repro.analysis import roofline as rl
    from repro.configs import get_config, SHAPES
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = mesh.devices.size
    t0 = time.time()

    if shape_name == "compress":
        return _lower_compress(cfg, mesh, chips)

    shape = SHAPES[shape_name]
    kind = shape.kind
    # ≤20B: full-batch step (grad-accum carry costs more than activations);
    # bigger: accumulate over micro-batches to bound a2a/attention transients.
    n = cfg.param_count()
    default_micro = "16" if n > 100e9 else ("8" if n > 20e9 else "1")
    n_micro = int(os.environ.get("DRYRUN_MICRO", default_micro))

    # 1) production lowering: compile + memory proof
    lowered, model_flops = _build_lowered(cfg, mesh, shape, kind,
                                          unroll=False, n_micro=n_micro)
    compiled = lowered.compile()
    mem = _mem_dict(compiled.memory_analysis())
    print(f"memory_analysis: {mem}")
    raw = _costs_of(compiled)

    # 2) cost lowerings: reduced-depth unrolled + linear fit. The microbatch
    # loop is unrolled too (unroll_accum) so per-microbatch FSDP re-gathers
    # are counted — total cost is NOT microbatch-invariant.
    fit = _fit_costs(cfg, mesh, shape, kind, n_micro)
    print(f"cost (scan-corrected): flops={fit['flops']:.3e} "
          f"bytes={fit['bytes']:.3e} coll={fit['collective_total']:.3e}")

    roof = rl.Roofline(flops_per_device=fit["flops"],
                       bytes_per_device=fit["bytes"],
                       collective_per_device=fit["collective_total"],
                       chips=chips, model_flops=model_flops)
    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "chips": chips, "kind": kind,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "memory": mem,
        "raw_scan_costs": raw,        # uncorrected, for reference
        "cost_fit": {k: fit[k] for k in
                     ("flops", "bytes", "collective_total", "collectives",
                      "fit_depths")},
        "roofline": roof.to_dict(),
        "compile_seconds": time.time() - t0,
    }


def _lower_compress(cfg, mesh, chips) -> dict:
    """Extra cell: the AWP compression step itself on the arch's largest
    linear — row-sharded (zero-collective) or column-sharded when replicated
    C would not fit (d_in > 46k). Loop unrolled ⇒ costs are exact."""
    import jax
    import jax.numpy as jnp
    from repro.analysis import roofline as rl
    from repro.core import distributed as dist
    from repro.core import projections as proj
    from repro.sharding import rules_for_cell

    d_model = cfg.d_model
    if cfg.family == "moe":
        d_out, d_in = cfg.d_ff, d_model
    elif cfg.family in ("ssm", "hybrid"):
        d_out, d_in = 2 * cfg.d_inner, d_model
    else:
        d_out, d_in = d_model, cfg.d_ff or d_model   # down-proj: largest fan-in
    rules = rules_for_cell(mesh, "dense", "compress")
    iters, k, eta = 20, d_in // 2, 1e-3
    # v2 schedule (§Perf compress hillclimb, iteration 2): rows over 'data'
    # only — 16× more rows per device lifts arithmetic intensity from
    # ~8 FLOP/B to ~128 (256 w/ bf16 C); the freed 'model' axis runs other
    # layers in parallel (whole-model compression is layer-parallel).
    sched = os.environ.get("DRYRUN_COMPRESS_SCHED", "v2")
    # iteration 2b (§Perf): bf16 C halves HBM reads on TPU, but the CPU cost
    # backend inserts a bf16→f32 convert copy per iteration (no native bf16
    # dot), tripling counted bytes — keep the counted model f32 and note the
    # TPU-side bf16 win separately.
    c_dtype = jnp.float32
    w_sds = jax.ShapeDtypeStruct((d_out, d_in), jnp.float32)
    c_sds = jax.ShapeDtypeStruct((d_in, d_in), c_dtype)
    t0 = time.time()

    def unrolled_run(w, c):
        theta = proj.topk_row(w, k)
        for _ in range(iters):
            z = theta + eta * (w - theta).astype(c.dtype) @ c
            theta = proj.topk_row(z.astype(jnp.float32), k)
        return theta

    if d_in <= 46_000:
        if sched == "v2":
            from repro.sharding import ShardingRules
            v2_rules = ShardingRules(mesh=mesh, batch_axes=rules.batch_axes,
                                     tp_axis=rules.tp_axis,
                                     fsdp_axes=rules.fsdp_axes,
                                     rows_axes=("data",))
            (in_w, in_c), out_sh = dist.rowsharded_shardings(v2_rules, d_out)
        else:
            (in_w, in_c), out_sh = dist.rowsharded_shardings(rules, d_out)
        with jax.set_mesh(mesh):
            lowered = jax.jit(unrolled_run, in_shardings=(in_w, in_c),
                              out_shardings=out_sh).lower(w_sds, c_sds)
        schedule = f"row-sharded (zero-collective, {sched})"
    else:
        run = dist.awp_prune_colsharded_fn(k, eta, iters, rules)
        with jax.set_mesh(mesh):
            lowered = jax.jit(run).lower(w_sds, c_sds)
        schedule = "column-sharded C (psum per iteration)"
    compiled = lowered.compile()
    costs = _costs_of(compiled)
    if schedule.startswith("column"):
        # the col-sharded loop is a scan: scale body costs by iters
        for kk in ("flops", "bytes", "collective_total"):
            costs[kk] *= iters
        costs["collectives"] = {kk: v * iters
                                for kk, v in costs["collectives"].items()}
    if "v2" in schedule:
        # v2 row-shards over 'data' only: one layer occupies a 16-chip slice
        # and the model axis runs 16 layers concurrently — normalize the
        # roofline to the slice (per-device costs already reflect it).
        chips = mesh.shape["data"]
    model_flops = iters * 2.0 * d_out * d_in * d_in
    roof = rl.Roofline(flops_per_device=costs["flops"],
                       bytes_per_device=costs["bytes"],
                       collective_per_device=costs["collective_total"],
                       chips=chips, model_flops=model_flops)
    return {
        "arch": cfg.name, "shape": "compress", "mesh": "single",
        "chips": chips, "kind": "compress", "schedule": schedule,
        "layer": {"d_out": d_out, "d_in": d_in, "iters": iters},
        "memory": _mem_dict(compiled.memory_analysis()),
        "cost_fit": costs,
        "roofline": roof.to_dict(),
        "compile_seconds": time.time() - t0,
    }


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------

def all_cells(include_compress: bool):
    from repro.configs import get_config, list_archs, shapes_for
    cells = []
    for arch in list_archs(include_paper=False):
        cfg = get_config(arch)
        for shape in shapes_for(cfg):
            cells.append((arch, shape.name))
        if include_compress:
            cells.append((arch, "compress"))
    return cells


def orchestrate(meshes, include_compress: bool, timeout: int):
    done, failed = [], []
    cells = all_cells(include_compress)
    for mesh_name in meshes:
        for arch, shape in cells:
            if shape == "compress" and mesh_name == "multi":
                continue                       # compress rooflined single-pod
            path = _out_path(mesh_name, arch, shape)
            if os.path.exists(path):
                done.append((mesh_name, arch, shape, "cached"))
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh_name]
            print(f"[dryrun] {mesh_name} {arch} {shape} ...", flush=True)
            try:
                r = subprocess.run(cmd, timeout=timeout, capture_output=True,
                                   text=True)
                if r.returncode == 0:
                    done.append((mesh_name, arch, shape, "ok"))
                else:
                    failed.append((mesh_name, arch, shape,
                                   r.stderr.strip().splitlines()[-1]
                                   if r.stderr.strip() else "nonzero exit"))
                    print(r.stderr[-2000:], flush=True)
            except subprocess.TimeoutExpired:
                failed.append((mesh_name, arch, shape, f"timeout {timeout}s"))
    print(f"\n=== dry-run summary: {len(done)} ok, {len(failed)} failed ===")
    for f in failed:
        print("FAILED:", f)
    return 0 if not failed else 1


def _pin_virtual_cpu_devices() -> None:
    """512 virtual CPU devices for the lowering; inherited by the per-cell
    children ``orchestrate`` starts. Must run before JAX initializes."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")


def main():
    _pin_virtual_cpu_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--compress", action="store_true",
                    help="include AWP compress-step cells")
    ap.add_argument("--timeout", type=int, default=5400)
    args = ap.parse_args()

    if args.all:
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        sys.exit(orchestrate(meshes, args.compress, args.timeout))

    assert args.arch and args.shape and args.mesh != "both"
    try:
        result = run_cell(args.arch, args.shape, args.mesh)
    except Exception:
        traceback.print_exc()
        sys.exit(1)
    path = _out_path(args.mesh, args.arch, args.shape)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    print(f"[dryrun] wrote {path}")
    r = result["roofline"]
    print(f"[dryrun] {args.arch} {args.shape} {args.mesh}: "
          f"compute={r['t_compute_s']:.4f}s memory={r['t_memory_s']:.4f}s "
          f"collective={r['t_collective_s']:.4f}s -> {r['bottleneck']}")


if __name__ == "__main__":
    main()
