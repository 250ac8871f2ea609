"""Runtime-sanitizer pass: the compression core under ``jax_debug_nans``
and ``jax_enable_checks``.

Why a DEDICATED, separately-marked invocation instead of flipping the
sanitizers on for everything:

* ``jax_debug_nans`` re-executes every primitive op-by-op (de-optimized)
  whenever an output contains NaN, and disables donation-friendly
  whole-program execution — the engine suites assert *compile counts* and
  the one-transfer-per-step contract, both of which the sanitizer's
  re-execution machinery perturbs;
* the serving attention path masks with intentional ``-inf`` logits and
  the fault-injection chaos suite (test_faults.py) injects NaNs ON
  PURPOSE to prove step() contains them — under ``jax_debug_nans`` those
  tests would abort inside jax instead of exercising our handling;
* ``jax_enable_checks`` adds per-op invariant checking that changes
  timings enough to matter for the bench smokes.

So the bit-parity/contract suites run clean-config, and this module — run
as its own CI step via ``-m sanitizers`` — sweeps the numeric core
(PGD pruning, quantization, batched engine, calibration) where a silent
NaN would corrupt results rather than crash.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import calibration, registry
from repro.core.specs import PruneSpec, QuantSpec

pytestmark = pytest.mark.sanitizers


@pytest.fixture(autouse=True)
def jax_sanitizers():
    """Enable debug_nans + enable_checks for this module only, restoring
    the clean config afterwards whatever happens. Compiled programs cached
    by earlier tests in the process are dropped first: a cached one can be
    reused under debug_nans without its NaN check."""
    old_nans = jax.config.jax_debug_nans
    old_checks = jax.config.jax_enable_checks
    jax.clear_caches()
    jax.config.update("jax_debug_nans", True)
    jax.config.update("jax_enable_checks", True)
    try:
        yield
    finally:
        jax.config.update("jax_debug_nans", old_nans)
        jax.config.update("jax_enable_checks", old_checks)
        jax.clear_caches()


@pytest.fixture()
def layer():
    rng = np.random.default_rng(42)
    w = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
    stats = calibration.init(32)
    for _ in range(3):
        acts = jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32))
        stats = calibration.update(stats, acts)
    return w, stats


def test_awp_prune_nan_free_under_debug_nans(layer):
    w, stats = layer
    fn = registry.get_method("awp_prune")
    res = fn(w, stats, PruneSpec(method="awp_prune", ratio=0.5))
    theta = np.asarray(res.theta)
    assert np.isfinite(theta).all()
    assert (theta != 0).sum() <= theta.size // 2 + theta.shape[0]


def test_awp_quant_nan_free_under_debug_nans(layer):
    w, stats = layer
    fn = registry.get_method("awp_quant")
    res = fn(w, stats, QuantSpec(method="awp_quant", bits=4, group_size=16))
    assert np.isfinite(np.asarray(res.theta)).all()
    assert res.qtensor is not None
    assert np.isfinite(np.asarray(res.qtensor.dequant())).all()


def test_calibration_covariance_damped_under_checks(layer):
    _w, stats = layer
    cov = calibration.covariance(stats, damp=0.01)
    assert np.isfinite(np.asarray(cov)).all()


def test_debug_nans_actually_armed():
    """Guard against the fixture silently not taking effect: an injected
    NaN must abort — otherwise this whole module is vacuous."""
    with pytest.raises(FloatingPointError):
        x = jnp.zeros((4,))
        jax.block_until_ready(x / x)
