"""Launcher contracts: compile-cache placement, the dry-run's import-time
hygiene, profiler windows that fail loudly, and serve's exit status."""
import os

import jax
import pytest

from repro.launch import compile_cache
from repro.obs import StepTraceWindow, trace_window

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_dryrun_import_sets_no_flags(monkeypatch):
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    import repro.launch.dryrun  # noqa: F401
    assert "XLA_FLAGS" not in os.environ


def test_profiler_window_that_cannot_start_raises(tmp_path):
    with trace_window(str(tmp_path / "a")):
        with pytest.raises(RuntimeError):
            with trace_window(str(tmp_path / "b")):
                pass
        with pytest.raises(RuntimeError):
            StepTraceWindow(str(tmp_path / "c"), steps=1).start()


def test_serve_exits_nonzero_when_a_request_errors(monkeypatch):
    from repro.launch import serve
    from repro.serving import Engine, RequestStatus

    run = Engine.run

    def run_with_one_error(self, *a, **kw):
        results = run(self, *a, **kw)
        if results and results[0].rid >= 0:        # not a warmup clone
            results[0].status = RequestStatus.ERROR.value
            results[0].error = "injected"
        return results

    monkeypatch.setattr(Engine, "run", run_with_one_error)
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "")
    monkeypatch.setattr("sys.argv", [
        "serve", "--tiny", "--arch", "llama32-1b", "--engine", "continuous",
        "--requests", "3", "--slots", "2", "--prompt-len", "8", "--gen", "3",
        "--buckets", "8"])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code not in (0, None)
