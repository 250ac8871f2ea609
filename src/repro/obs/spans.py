"""Names in the profiler trace: scopes of the step programs, host spans of
the engine's step.

One vocabulary, written here and read by whatever reduces a trace:

* ``SCOPES`` — ``jax.named_scope`` names inside the serving step programs.
  They land in each HLO instruction's ``metadata.op_name`` (and so in the
  trace's ``Hlo Proto``); the innermost one names the layer an op belongs
  to. Scopes change metadata only, never an op.
* ``SPANS`` — ``jax.profiler.TraceAnnotation`` spans that ``Engine.step``
  writes into the host plane, on the profiler's clock. ``engine.step`` is a
  ``StepTraceAnnotation`` numbered by the decode-step counter; every other
  span nests inside it.

With no profiler session a span costs about a microsecond of host time,
and its arguments are not computed: pass them as a callable, which runs
only while a session records. ``jax`` is imported on first use, so
``repro.obs`` stays import-free.
"""

from __future__ import annotations

from typing import Callable, Optional

__all__ = ["SCOPES", "SPANS", "scope", "span", "step_span"]

# step-program scopes, in the order a decode step meets them
SCOPES = (
    "embed",        # token embedding gather
    "blocks",       # the layer loop (lax.scan, or its unrolled loop)
    "attn_in",      # pre-attention norm, q/k/v projections, rope
    "kv_write",     # the new tokens' K/V into the cache (paged or slot rows)
    "attn",         # flash_decode, paged or prefill attention
    "attn_out",     # the o-projection
    "mlp",          # the MLP block, norm included
    "head",         # final norm, vocabulary head, pad mask
    "sample",       # sampling and the finite-logit flag
    "kv_splice",    # prefill's mini-cache splice (write_pages)
)

# Engine.step phases
SPANS = (
    "engine.step",          # one Engine.step; step_num = decode steps so far
    "engine.expire",        # deadline expiry and the queue-depth sample
    "engine.admit",         # admission: holds prefill/chunk/preempt/resume
    "engine.prefill",       # one batched prefill dispatch: rows, bucket,
    #                         prompt_tokens
    "engine.chunk",         # one chunk dispatch (tokens)
    "engine.preempt",       # one preemption: spill to host
    "engine.resume",        # one resume: restore from host
    "engine.extend",        # pages for the next decode write (paged)
    "engine.decode",        # decode argument preparation and dispatch:
    #                         rows, ctx_tokens
    "engine.decode.wait",   # the one device-to-host transfer of the step
    "engine.commit",        # per-slot token bookkeeping and finishes
)
_SCOPES, _SPANS = frozenset(SCOPES), frozenset(SPANS)

_annotation = None      # jax.profiler.TraceAnnotation, bound on first use
_step_annotation = None  # jax.profiler.StepTraceAnnotation
_is_enabled = None      # the profiler's "is a session recording" check


def _bind() -> None:
    global _annotation, _step_annotation, _is_enabled
    import jax
    from jax._src.lib import _profiler
    _annotation = jax.profiler.TraceAnnotation
    _step_annotation = jax.profiler.StepTraceAnnotation
    _is_enabled = _profiler.TraceMe.is_enabled


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES`."""
    if name not in _SCOPES:
        raise ValueError(f"{name!r} is not a step-program scope")
    import jax
    return jax.named_scope(name)


def span(name: str, args: Optional[Callable[[], dict]] = None):
    """A host span of :data:`SPANS`; ``args()`` gives its arguments and is
    called only while a session records."""
    if name not in _SPANS:
        raise ValueError(f"{name!r} is not an engine span")
    if _annotation is None:
        _bind()
    if args is not None and _is_enabled():
        return _annotation(name, **args())
    return _annotation(name)


def step_span(step_num: int):
    """The ``engine.step`` span, a ``StepTraceAnnotation`` numbered
    ``step_num``."""
    if _step_annotation is None:
        _bind()
    return _step_annotation("engine.step", step_num=step_num)
