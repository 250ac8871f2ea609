"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. Exits non-zero, printing no result, unless JAX's
devices are TPUs and as many as the cell asks for. The last line of standard
output is the run's JSON result; see ``bench/harness/cli.py``.
"""
import os
import sys
import time

STARTED = time.perf_counter()          # set-up is timed from here

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]   # not bench/ itself

if __name__ == "__main__":
    from bench.harness import cli
    sys.exit(cli.main(sys.argv[1:], ROOT, STARTED))
