"""Opt-in jax.profiler trace windows.

Two shapes:

* ``trace_window(log_dir)`` — context manager around one block of work
  (used by ``compress_model(profile_block=...)``).
* ``StepTraceWindow(log_dir, steps)`` — start/step/stop object for wrapping
  the first N engine steps (used by ``serve.py --profile-steps``); its
  ``on_step`` method plugs into ``Engine.run(step_hook=...)``.

Both are no-ops when the directory is empty. A window that was asked for
and cannot start raises: a measurement run must not go on unprofiled in
silence. Both record without the Python function tracer (it bloats the
trace and slows the host) and with host tracer level 2, so the trace holds
the device ops and the engine's ``engine.*`` spans (``repro.obs.spans``).
"""

from __future__ import annotations

import contextlib

__all__ = ["trace_window", "StepTraceWindow"]


def _start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


@contextlib.contextmanager
def trace_window(log_dir: str):
    """Profile the enclosed block into ``log_dir``; no-op if dir is empty."""
    if not log_dir:
        yield False
        return
    import jax
    _start(log_dir)
    try:
        yield True
    finally:
        jax.profiler.stop_trace()


class StepTraceWindow:
    """Profile the first ``steps`` engine steps after ``start()``."""

    def __init__(self, log_dir: str, steps: int):
        self.log_dir = log_dir
        self.steps = steps
        self._remaining = 0
        self._active = False

    @property
    def enabled(self) -> bool:
        return bool(self.log_dir) and self.steps > 0

    def start(self) -> None:
        if not self.enabled or self._active:
            return
        _start(self.log_dir)
        self._active = True
        self._remaining = self.steps

    def on_step(self, engine=None) -> None:
        if not self._active:
            return
        self._remaining -= 1
        if self._remaining <= 0:
            self.stop()

    def stop(self) -> None:
        if self._active:
            import jax
            jax.profiler.stop_trace()
            self._active = False
