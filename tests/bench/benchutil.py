"""Helpers and fixtures for the benchmark's own tests: the checkout root on
the path, and a tiny serving cell added to a copy of the benchmark as data
files only (a configuration file, a traffic file and BENCHMARK.json
entries). Test modules import the fixtures they use by name; this
directory has no conftest.py, whose module name would shadow the suite's."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_CELL = "tiny-granite.serve.chat"


def add_tiny_cell(root: str) -> None:
    """A tiny cell of the same family, made of new files and entries."""
    cfg = json.load(open(os.path.join(root, "bench/configs/granite-8b.json")))
    cfg["name"] = "tiny-granite"
    cfg["model"].update(hidden_size=128, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=2,
                        head_dim=32, intermediate_size=256, vocab_size=512)
    cfg["engine"].update(num_slots=4, max_len=256, prompt_buckets=[32, 64],
                         num_pages=64)
    with open(os.path.join(root, "bench/configs/tiny-granite.json"), "w") as f:
        json.dump(cfg, f)
    mix = json.load(open(os.path.join(root, "bench/traffic/assist.json")))
    mix.update(rate_per_s=2.0, preroll_requests=2, trace_window_s=[0.5, 1.0],
               prompt={"median": 40, "sigma": 0.8, "min": 8, "max": 120},
               output={"median": 12, "sigma": 0.6, "min": 4, "max": 32})
    mix["check"] = {"served_tokens": 200, "served_token_gap": 1e-3}
    with open(os.path.join(root, "bench/traffic/tiny-chat.json"), "w") as f:
        json.dump(mix, f)
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "tiny-granite", "source": "test",
                             "file": "bench/configs/tiny-granite.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny-granite",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(TINY_CELL)
    with open(path, "w") as f:
        json.dump(bench, f)


def cpu_device(chips: int) -> dict:
    """Stands in for the TPU check in tests; the kind keys bench/peaks.json."""
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": chips}


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    """The harness points JAX's persistent cache into its checkout; tests
    leave the process-wide setting alone."""
    from bench.harness import cli
    monkeypatch.setattr(cli, "use_compile_cache", lambda root: None)


@pytest.fixture
def bench_root(tmp_path):
    """A checkout holding BENCHMARK.json, bench/ and the program, with the
    tiny cell added."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    add_tiny_cell(root)
    return root
