"""The harness's own host spans: ``jax.profiler.TraceAnnotation`` around its
calls into the program (``submit``, ``Engine.step``) and around its waits
(``idle``), written into the profiler's trace on the device clock's time
base. Off, and free, in runs without ``--trace 1``. Spans inside the
program are not the benchmark's to add."""
from __future__ import annotations

import contextlib


class Spans:
    def __init__(self, enabled: bool):
        self.enabled = enabled

    def __call__(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)
