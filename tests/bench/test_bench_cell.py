"""One run of a tiny serving cell on the CPU, through the harness's own
entry points, with the TPU check stubbed here in the test: the result
line, a cell and a metric added as new files only, and the refusal to run
without a TPU."""
import io
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import trace_reduce
from bench.harness import cli, serve
from benchutil import (ROOT, TINY_CELL, bench_root,  # noqa: F401
                       cpu_device, no_persistent_cache)



def run(root, trace=False, **kw):
    return cli.run_cell(root, TINY_CELL, 2 ** 40 + 17, 2.0, trace,
                        time.perf_counter(), device_check=cpu_device, **kw)


def test_result_line_has_exactly_the_contract_keys(bench_root):
    r = run(bench_root)
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device",
                       "checks"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    bench = json.load(open(os.path.join(bench_root, "BENCHMARK.json")))
    assert set(r["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in r["metrics"]
    for m in r["metrics"].values():
        assert m["value"] > 0 and m["unit"] in ("ms", "s")
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    gap = r["checks"]["served_token_gap"]
    assert gap["value"] <= gap["limit"]
    json.dumps(r)


def test_traced_run_reports_per_layer_metrics_and_breakdown(bench_root,
                                                            monkeypatch):
    from test_bench_trace import unpacked
    chip = trace_reduce.reduce(unpacked(bench_root))
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda d: chip)
    # a per-layer metric added by a new reader file and an entry alone
    with open(os.path.join(bench_root, "bench/metrics/probe.tiny.py"),
              "w") as f:
        f.write("def read(ctx, peaks):\n    return 42.0\n")
    path = os.path.join(bench_root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["per_layer"].append({"name": "probe.tiny", "unit": "x",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "tpot_ms",
                               "workloads": [TINY_CELL]})
    json.dump(bench, open(path, "w"))
    r = run(bench_root, trace=True)
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device",
                       "breakdown", "checks"]
    assert r["metrics"]["probe.tiny"] == {"value": 42.0, "unit": "x"}
    assert {"idle_share.serve", "roofline.dequant_matmul.decode",
            "roofline.flash_decode", "mfu.decode"} <= set(r["metrics"])
    assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
    bd = r["breakdown"]
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_a_block_added_as_a_new_file_serves_the_cell(bench_root):
    """An architecture enters as one new block file that its configuration
    names; here a copy of the dense block under a name of its own."""
    blocks = os.path.join(bench_root, "bench/harness/blocks")
    shutil.copy(os.path.join(blocks, "dense.py"),
                os.path.join(blocks, "tiny_dense.py"))
    path = os.path.join(bench_root, "bench/configs/tiny-granite.json")
    cfg = json.load(open(path))
    cfg["block"] = "tiny_dense"
    json.dump(cfg, open(path, "w"))
    cell = cli.find_cell(bench_root, TINY_CELL)
    assert cell.block.__file__ == os.path.join(blocks, "tiny_dense.py")
    r = run(bench_root)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"tpot_ms", "setup_s"}


def test_no_tpu_exits_nonzero_before_any_work(bench_root, monkeypatch,
                                              capsys):
    def work(*a, **k):
        raise AssertionError("the cell ran without a TPU")
    monkeypatch.setattr(serve, "run", work)
    rc = cli.main(["--workload", TINY_CELL, "--seed", "1", "--seconds", "1",
                   "--trace", "0"], bench_root, time.perf_counter())
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err


def test_unknown_workload_and_device_are_errors(bench_root):
    with pytest.raises(cli.BenchError):
        cli.find_cell(bench_root, "no.such.cell")
    with pytest.raises(cli.BenchError):
        cli.peaks_for(bench_root, "TPU v0")


def test_bare_checkout_without_the_program_prints_no_result(tmp_path):
    bare = tmp_path / "bare"
    bare.mkdir()
    subprocess.run(["cp", "-r", os.path.join(ROOT, "bench"),
                    os.path.join(ROOT, "BENCHMARK.json"), str(bare)],
                   check=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "granite-8b.serve.assist", "--seed", "3",
                        "--seconds", "1", "--trace", "0"], cwd=bare,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()
