"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a serving cell can have, and the control's readings
run at a test's size. The TPU check is stubbed here in the test."""
import json
import os
import time

import numpy as np
import pytest

from bench import control
from bench.harness import cli
from benchutil import (TINY_CELL, bench_root,  # noqa: F401
                       cpu_device, no_persistent_cache)


def run(root, **kw):
    return cli.run_cell(root, TINY_CELL, 2 ** 35 + 9, 2.0, False,
                        time.perf_counter(), device_check=cpu_device, **kw)


def _altered_tokens(monkeypatch):
    """A token altered where it is produced: every sampled token moved
    half the vocabulary away."""
    from repro.serving import engine
    real = engine.sample_tokens

    def wrong(logits, *a, **k):
        v = logits.shape[-1]
        return (real(logits, *a, **k) + v // 2) % v
    monkeypatch.setattr(engine, "sample_tokens", wrong)


def _state_unchanged(monkeypatch):
    """A step that returns its state unchanged: decode hands back the KV
    cache it was given, so its tokens' keys and values are never written."""
    from repro.serving import engine
    real = engine.step_jit

    def broken(fn, **kw):
        if fn.__name__ == "decode_fn":
            def decode_fn(params, kv, *a):
                return fn(params, kv, *a)[0], kv
            return real(decode_fn, **kw)
        return real(fn, **kw)
    monkeypatch.setattr(engine, "step_jit", broken)


@pytest.mark.parametrize("fault", [_altered_tokens, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(bench_root, monkeypatch, fault):
    fault(monkeypatch)
    r = run(bench_root)
    gap = r["checks"]["served_token_gap"]
    assert r["correct"] is False and gap["value"] > gap["limit"]


def test_control_goes_through_the_cells_own_comparison(bench_root):
    r = run(bench_root, verify=control.readings)
    limit = json.load(open(os.path.join(
        bench_root, "bench/traffic/tiny-chat.json")))["check"][
            "served_token_gap"]
    ctl = r["checks"]["served_token_gap"]
    # the control's number is held against the cell's own limit, and it
    # alone can fail the run: every other reading's limit is infinite
    assert ctl["limit"] == limit
    assert r["correct"] is (ctl["value"] <= limit)
    extra = {k: v for k, v in r["checks"].items() if k != "served_token_gap"}
    assert all(v["limit"] == float("inf") for v in extra.values())
    got = {k: v["value"] for k, v in extra.items()}
    assert got["tokens"] > 0 and got["requests"] > 0
    # float32 on the CPU: the program serves at most a near twin of the
    # reference's best token; the control's lower precision exists only
    # on the TPU's matrix unit, so here it reads like the program
    assert got["program_gap"] <= limit
    assert np.isfinite(ctl["value"]) and np.isfinite(
        got["blocks_control_gap"])


def test_a_control_over_its_limit_is_not_correct(bench_root, monkeypatch):
    """The control's reading, pushed over the limit, turns the run false."""
    from bench.harness import serve
    real = serve.gaps
    monkeypatch.setattr(serve, "gaps", lambda st: real(st) + 1.0)
    r = run(bench_root, verify=control.readings)
    assert r["correct"] is False
    ctl = r["checks"]["served_token_gap"]
    assert ctl["value"] > ctl["limit"]
