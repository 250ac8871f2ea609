"""One run of one cell: find it by name, check the device, run it, print
the result.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``: the configuration's file, the block module
``bench/harness/blocks/<block>.py`` that the file's ``"block"`` names,
``bench/traffic/<traffic>.json`` (whose ``kind`` names the runner module
``bench.harness.<kind>``) and one reader ``bench/metrics/<metric>.py`` per
per-layer metric. Adding a cell, a configuration, an architecture or a
metric adds files and entries; it edits none.

A block module holds everything that depends on the architecture: its keys
of the published config (read strictly: a key of the file's ``model`` that
the block does not read is an error before any work), the seeded float
weights, the program's parameter tree, the plain reference of one block and
the model's operation count. Its contract is the docstring of
``bench/harness/blocks/__init__.py``; ``blocks/dense.py`` is the Llama
block granite-8b is served as.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared for ``correct``
with its limit. The same checks are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib
import importlib.util
import json
import os
import sys
import types
from typing import Any, Callable, Dict, List, Optional


class BenchError(RuntimeError):
    """A run that cannot give a result: no result line, non-zero exit."""


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``: it passes at or below limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                # the configuration's file
    block: types.ModuleType     # bench/harness/blocks/<block>.py
    dims: Any                   # block.Dims of the file's model
    traffic: dict               # bench/traffic/<traffic>.json
    end_to_end: List[dict]      # the BENCHMARK.json entries it reports
    per_layer: List[dict]


@dataclasses.dataclass
class Outcome:
    """What a runner hands back. ``values`` holds every end-to-end metric
    it measured; ``context`` is what the per-layer readers read."""
    values: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: int
    context: Any = None


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(root: str, name: str) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(root, cfg["file"]))
    if "block" not in config:
        raise BenchError(f"{cfg['file']} names no block")
    block = load_block(root, config["block"])
    dims = block.Dims.from_config(config["model"])
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))

    def reports(m: dict) -> bool:
        return name in m.get("workloads", [name])
    e2e = [m for m in bench["end_to_end"] if reports(m)]
    moved = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (name in m["workloads"] if "workloads" in m
               else m["moves"] in moved)]
    return Cell(name, int(w["chips"]), config, block, dims, traffic, e2e,
                per)


def _module(path: str, name: str):
    """The module at ``path``, registered as ``name`` (a dataclass looks
    its module up there)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


_load_block = functools.cache(_module)


def load_block(root: str, block: str):
    """The block module bench/harness/blocks/<block>.py, loaded once per
    path (its jitted reference keeps its compiled programs across runs)."""
    path = os.path.join(root, "bench", "harness", "blocks", block + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no block {block!r}: {path} is missing")
    return _load_block(os.path.abspath(path),
                       "bench_block_" + block.replace(".", "_"))


def reader(root: str, metric: str) -> Callable:
    """``read(context, peaks) -> float | None`` of bench/metrics/<metric>.py."""
    return _module(os.path.join(root, "bench", "metrics", metric + ".py"),
                   "bench_metric_" + metric.replace(".", "_")).read


def device_info(chips: int) -> dict:
    """JAX's devices, or BenchError unless they are TPUs and enough."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {d.platform!r} "
                         f"({d.device_kind})")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX has "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peaks_for(root: str, kind: str) -> dict:
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, holding every program however fast it compiled."""
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, started: float,
             device_check: Callable[[int], dict] = device_info,
             **runner_kw) -> dict:
    """The result of one run, as the dict printed on the last line.
    ``runner_kw`` go to the cell's runner (the control script swaps the
    comparison that way)."""
    cell = find_cell(root, workload)
    device = device_check(cell.chips)
    peaks = peaks_for(root, device["kind"])
    use_compile_cache(root)
    runner = importlib.import_module("bench.harness." + cell.traffic["kind"])
    out: Outcome = runner.run(cell, seed=seed, seconds=seconds, trace=trace,
                              started=started, root=root, **runner_kw)
    device = dict(device, memory_peak_bytes=out.memory_peak_bytes)
    result = {"correct": all(c.ok for c in out.checks),
              "attempted": out.attempted, "failed": out.failed}
    if trace:
        ctx = out.context
        metrics = {}
        for m in cell.per_layer:
            value = reader(root, m["name"])(ctx, peaks)
            if value is None:
                continue
            if m["unit"] == "%" and value > 100.0:
                raise BenchError(f"{m['name']} reads {value}% of a peak: "
                                 f"its operations or bytes are counted too "
                                 f"high, or its time leaves out work")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=ctx.trace.busy_s, window_s=ctx.trace.window_s)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = ctx.trace.breakdown()
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in out.values:
                raise BenchError(f"the runner measured no {m['name']}")
            metrics[m["name"]] = {"value": out.values[m["name"]],
                                  "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    return result


def main(argv, root: str, started: float) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(root, args.workload, args.seed, args.seconds,
                          bool(args.trace), started)
    except BenchError as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"[bench] check {name}: {c['value']!r} (limit {c['limit']!r}) "
              f"{ok}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
