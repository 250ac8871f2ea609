"""The control of a serving cell's ``correct``, and the program's own
readings, on the chip at the cell's size.

    python3 bench/control.py --workload <name> --seconds <s> --seeds <n> ...

For each seed, in one process: one run of the cell (set-up, window), then
on the same sampled finished requests
- the control: the reference computed at ``high`` (three bf16 passes, the
  nearest precision below the configuration's) put in the program's place.
  At each position it puts a token first; that token's gap below the
  ``highest`` reference's best is held against the cell's own limit as
  ``served_token_gap``, so a control run comes out ``correct: false``
  through the harness's own comparison;
- beside it, readings that cannot fail the run: the program's own gap
  (the reference at ``highest`` at every served token), and a second
  control that lowers only the blocks to ``high`` and keeps the head at
  ``highest``, with the cell's limit printed beside it.
One JSON line per seed, in the form of the benchmark's result line. The
benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]


def readings(seed, block, dims, recipe, picked, prompts, spec):
    import numpy as np
    from bench.harness import reference, serve
    from bench.harness.cli import Check
    seqs, starts = serve.sequences(picked, prompts)
    served = [np.asarray(r.tokens) for r in picked]

    def gap(targets):
        stats = reference.token_stats(seed, block, dims, recipe, seqs,
                                      starts, targets)
        return np.concatenate([serve.gaps(st) for st in stats])

    def firsts(**precision):
        return [st["argmax"] for st in reference.token_stats(
            seed, block, dims, recipe, seqs, starts, served, **precision)]
    prog = gap(served)
    ctrl = gap(firsts(precision="high"))
    blocks = gap(firsts(precision="high", head_precision="highest"))
    limit = spec["served_token_gap"]
    extra = {"tokens": prog.size, "requests": len(picked),
             "program_gap": prog.max(), "program_flips": (prog > 0).sum(),
             "control_flips": (ctrl > 0).sum(),
             "blocks_control_gap": blocks.max(),
             "blocks_control_flips": (blocks > 0).sum()}
    print(f"[control] blocks-only control: gap {float(blocks.max())!r} "
          f"(limit {limit!r}); {json.dumps({k: float(v) for k, v in extra.items()})}",
          file=sys.stderr, flush=True)
    return ([Check("served_token_gap", float(ctrl.max()), limit)]
            + [Check(k, float(v), float("inf")) for k, v in extra.items()])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    from bench.harness import cli
    for seed in args.seeds:
        t = time.perf_counter()
        r = cli.run_cell(ROOT, args.workload, seed, args.seconds, False, t,
                         verify=readings)
        print(json.dumps({"seed": seed, **r,
                          "seconds": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()
