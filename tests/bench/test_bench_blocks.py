"""The block a configuration names (``bench/harness/blocks/``): its keys
are read strictly, before any work, and the dense block makes the tiny
cell's packed tree and reference statistics bit for bit as they were made
before the block had a module of its own."""
import hashlib
import json
import os
import time

import jax
import numpy as np
import pytest

from bench.harness import cli, reference, serve
from bench.harness import weights as W
from benchutil import (TINY_CELL, bench_root,  # noqa: F401
                       no_persistent_cache)

SEED = 2 ** 33 + 5
# sha256 of every leaf (path, dtype, shape, bytes) on the CPU, recorded
# from weights.py and reference.py as they were before the dense block had
# a module of its own
TREE = "a2ed07d6e5b73e51528487847cfee957f9a77dbbd0bf5a6ec19cd03f049b234d"
STATS = "9037c853a40069399223fdaf51dc1128a060a0a1708bc4b9c3bd1091e7551d02"


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_tiny_cell_tree_and_reference_are_the_parents(bench_root):
    cell = cli.find_cell(bench_root, TINY_CELL)
    recipe = W.Recipe.from_config(cell.config["weights"])
    assert digest(W.served_params(SEED, cell.block, cell.dims,
                                  recipe)) == TREE
    rng = np.random.default_rng(7)
    seqs = [rng.integers(0, 512, n).astype(np.int32) for n in (37, 300)]
    starts = [5, 120]
    targets = [rng.integers(0, 512, len(s) - st).astype(np.int32)
               for s, st in zip(seqs, starts)]
    assert digest(reference.token_stats(SEED, cell.block, cell.dims, recipe,
                                        seqs, starts, targets)) == STATS


def _edit_model(root, edit):
    path = os.path.join(root, "bench/configs/tiny-granite.json")
    cfg = json.load(open(path))
    edit(cfg)
    json.dump(cfg, open(path, "w"))


@pytest.mark.parametrize("edit,named", [
    (lambda c: c["model"].update(use_bias=True), "use_bias"),
    (lambda c: c["model"].pop("rope_theta"), "rope_theta"),
    (lambda c: c["model"].update(tie_word_embeddings=True),
     "tie_word_embeddings"),
    (lambda c: c.update(block="no_such_block"), "no_such_block"),
], ids=["unread_key", "missing_key", "tied_head", "unknown_block"])
def test_a_model_the_block_cannot_read_is_refused_before_any_work(
        bench_root, monkeypatch, capsys, edit, named):
    _edit_model(bench_root, edit)

    def work(*a, **k):
        raise AssertionError("the cell ran")
    monkeypatch.setattr(serve, "run", work)
    with pytest.raises(cli.BenchError, match=named):
        cli.find_cell(bench_root, TINY_CELL)
    rc = cli.main(["--workload", TINY_CELL, "--seed", "1", "--seconds", "1",
                   "--trace", "0"], bench_root, time.perf_counter())
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert named in out.err
