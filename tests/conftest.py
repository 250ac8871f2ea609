import functools
import inspect
import itertools
import os
import random
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
sys.path.insert(0, SRC)


# ---------------------------------------------------------------------------
# hypothesis fallback: on a clean interpreter (no `pip install hypothesis`)
# provide a minimal shim so @given-based tests still run — each test executes
# over a few fixed, deterministic examples instead of a random search.
# conftest is imported before the test modules, so the fake lands in
# sys.modules ahead of their `from hypothesis import ...`.
# ---------------------------------------------------------------------------

try:
    import hypothesis  # noqa: F401
except ImportError:
    _N_EXAMPLES = 5

    class _Strategy:
        def __init__(self, values):
            self._values = list(values)

        def examples(self, n):
            return list(itertools.islice(itertools.cycle(self._values), n))

    def _integers(min_value, max_value):
        rng = random.Random(0xA3F ^ min_value ^ max_value)
        vals = [min_value, max_value, (min_value + max_value) // 2]
        vals += [rng.randint(min_value, max_value) for _ in range(7)]
        return _Strategy(vals)

    def _sampled_from(seq):
        return _Strategy(seq)

    def _given(*strategies):
        def deco(fn):
            @functools.wraps(fn)
            def wrapper():
                cols = [s.examples(_N_EXAMPLES) for s in strategies]
                for values in zip(*cols):
                    fn(*values)
            # hide the wrapped signature or pytest would treat the
            # strategy-filled parameters as fixtures
            del wrapper.__wrapped__
            wrapper.__signature__ = inspect.Signature()
            return wrapper
        return deco

    def _settings(**_kwargs):
        return lambda fn: fn                  # max_examples/deadline: no-op

    _st = types.ModuleType("hypothesis.strategies")
    _st.integers = _integers
    _st.sampled_from = _sampled_from
    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.settings = _settings
    _hyp.strategies = _st
    _hyp.__is_shim__ = True
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st


def run_multidevice(code: str, n_devices: int = 8, timeout: int = 600):
    """Run a python snippet in a subprocess with N fake CPU devices.

    The main pytest process must keep seeing ONE device (smoke tests and
    benches depend on it), so every multi-device test runs isolated."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", code], env=env, timeout=timeout,
                       capture_output=True, text=True)
    assert r.returncode == 0, f"subprocess failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def assert_flat_compiles():
    """Context manager asserting an engine compiles NOTHING inside the
    block — the runtime counterpart of the ``recompile-hazard`` lint.

        with assert_flat_compiles(engine, compiled):   # baseline optional
            engine.run()

    Compares ``engine.compile_counts()`` after the block against the
    baseline (default: counts on entry) per program kind."""
    import contextlib

    @contextlib.contextmanager
    def guard(engine, baseline=None):
        before = dict(baseline if baseline is not None
                      else engine.compile_counts())
        yield before
        after = engine.compile_counts()
        assert set(after) == set(before), (
            f"compile_counts keys changed: {sorted(before)} -> "
            f"{sorted(after)}")
        for kind, n in after.items():
            want = before[kind]
            assert n == want, (
                f"post-warmup recompile: {kind} compiled {n} time(s), "
                f"expected {want}")

    return guard


POOL_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def pool_carry_faults(engine, hlo: str) -> list:
    """How an optimized paged step program (HLO text, parameters in the
    order ``(params, kv, ...)``, outputs ``(out, kv)``) moves the engine's
    KV pools as data: each copy, dynamic-slice or dynamic-update-slice with
    a pool-shaped result — ending in a pool plane's per-layer shape
    (pages, page size, KV heads, ·), so per-layer slices and whole stacks
    alike, or in the stack flattened to (layers · pages, page size, ...) —
    and each pool plane not aliased input to output. Empty when the pools
    stay in place (the token scatter is not a move)."""
    import re

    import jax

    planes = jax.tree.leaves(engine.kv)
    tails = {tuple(p.shape[1:]) for p in planes}
    tails |= {(p.shape[0] * p.shape[1],) + tuple(p.shape[2:]) for p in planes}
    faults = []
    for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* "
                         r"([\w-]+)\(", hlo, re.M):
        shape = tuple(int(x) for x in m.group(2).split(",") if x)
        if m.group(3) in POOL_MOVES and any(shape[-len(t):] == t
                                             for t in tails):
            faults.append(f"{m.group(3)} {shape} %{m.group(1)}")
    header = hlo.splitlines()[0]
    aliased = set(re.findall(r"\{(\d+)\}: \((\d+), \{\}", header))
    n_params = len(jax.tree.leaves(engine.params))
    for i in range(len(planes)):
        if (str(1 + i), str(n_params + i)) not in aliased:
            faults.append(f"pool plane {i} (parameter {n_params + i}) is "
                          f"not aliased to output {1 + i}")
    return faults
