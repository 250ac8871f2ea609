"""Names in the profiler trace (``repro.obs.spans``): the engine's host
spans, recorded in a CPU profiler session of a tiny paged engine, and the
step programs' scopes, which change metadata only."""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_tiny_config
from repro.models import build_model
from repro.obs import StepTraceWindow, snapshot_series, trace_window
from repro.obs.spans import SCOPES, SPANS, scope, span
from repro.serving import Engine, EngineConfig, GenerationRequest

BLOCK_SCOPES = {"attn_in", "kv_write", "attn", "attn_out", "mlp"}
# a pool too small for all three requests at full length: decode extension
# preempts the youngest, which later resumes
PAGED = dict(num_slots=3, max_len=32, prompt_buckets=(8, 16), page_size=4,
             kv_layout="paged", num_pages=12, prefix_caching=False)


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = get_tiny_config("llama32-1b")
    model = build_model(cfg, remat=False)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _requests(cfg):
    """Two short prompts (one batched prefill), one beyond the largest
    bucket (chunked), each decoding until the pool runs dry."""
    rng = np.random.default_rng(7)
    lens, gens = (6, 7, 20), (12, 12, 6)
    return [GenerationRequest(
        rid=i, prompt=rng.integers(1, cfg.vocab_size, n).astype(np.int32),
        max_new_tokens=g) for i, (n, g) in enumerate(zip(lens, gens))]


def _host_events(log_dir):
    """(start, end, name, args) of every host event in the trace."""
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return sorted((ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events)


def _host_spans(log_dir):
    """The engine's spans among them."""
    return [ev for ev in _host_events(log_dir)
            if ev[2].startswith("engine.")]


def _drive(model, params, reqs, log_dir=None):
    """Warm up, then serve ``reqs`` (inside a profiler session when
    ``log_dir``); each decode dispatch records the engine's own count of
    active slots and their live context."""
    eng = Engine(model, params, EngineConfig(**PAGED))
    eng.warmup(reqs)
    seen = []
    decode = eng._decode

    def recording_decode(*args):
        active = eng.scheduler.active_slots()
        seen.append((len(active), int(sum(eng._pos[s] + 1 for s in active))))
        return decode(*args)

    eng._decode = recording_decode
    with trace_window(log_dir or ""):
        for r in reqs:
            eng.submit(r)
        out = {r.rid: list(r.tokens) for r in eng.run()}
    eng._decode = decode
    return eng, out, seen


@pytest.fixture(scope="module")
def traced(tiny_lm, tmp_path_factory):
    cfg, model, params = tiny_lm
    log_dir = str(tmp_path_factory.mktemp("spans"))
    eng, out, seen = _drive(model, params, _requests(cfg), log_dir)
    return eng, out, seen, _host_spans(log_dir)


def test_span_names_are_exactly_the_documented_set(traced):
    eng, _, _, spans = traced
    assert eng.preemptions > 0 and eng.resumes > 0 and eng.chunk_dispatches
    assert {n for _, _, n, _ in spans} == set(SPANS)


def test_every_engine_span_nests_in_an_engine_step(traced):
    _, _, _, spans = traced
    steps = [(s, e) for s, e, n, _ in spans if n == "engine.step"]
    assert steps
    for s, e, n, _ in spans:
        if n != "engine.step":
            assert any(a <= s and e <= b for a, b in steps), n
    nums = [a["step_num"] for _, _, n, a in spans if n == "engine.step"]
    assert nums == sorted(nums) and nums[-1] == nums[0] + len(set(nums)) - 1


def test_decode_span_args_are_the_engines_own_counts(traced):
    _, _, seen, spans = traced
    got = [(a["rows"], a["ctx_tokens"]) for _, _, n, a in spans
           if n == "engine.decode"]
    assert got == seen and len(got) > 3


def test_prefill_and_chunk_span_args(traced, tiny_lm):
    eng, _, _, spans = traced
    cfg = tiny_lm[0]
    prefills = [a for _, _, n, a in spans if n == "engine.prefill"]
    chunks = [a["tokens"] for _, _, n, a in spans if n == "engine.chunk"]
    assert len(prefills) == eng.prefill_dispatches
    assert len(chunks) == eng.chunk_dispatches
    assert {"rows", "bucket", "prompt_tokens"} == set(prefills[0])
    assert prefills[0] == {"rows": 2, "bucket": 8, "prompt_tokens": 13}
    # the counter holds every prompt token run through both programs
    total = sum(a["prompt_tokens"] for a in prefills) + sum(chunks)
    counted = snapshot_series(eng.metrics_snapshot(), "counters",
                              "engine_prefill_tokens_total")["value"]
    assert counted == total == sum(
        len(r.prompt) for r in _requests(cfg))


def test_a_profiler_session_changes_no_token_and_no_compile(tiny_lm,
                                                            tmp_path):
    cfg, model, params = tiny_lm
    reqs = _requests(cfg)
    on, out_on, _ = _drive(model, params, reqs, str(tmp_path))
    off, out_off, _ = _drive(model, params, reqs)
    assert out_on == out_off
    assert on.compile_counts() == off.compile_counts()


@pytest.mark.parametrize("window", ["trace_window", "StepTraceWindow"])
def test_profiler_windows_keep_spans_without_the_python_tracer(tmp_path,
                                                               window):
    def work():
        with span("engine.commit"):
            jnp.ones(3).block_until_ready()

    if window == "trace_window":
        with trace_window(str(tmp_path)):
            work()
    else:
        w = StepTraceWindow(str(tmp_path), steps=1)
        w.start()
        work()
        w.on_step()
    names = [ev[2] for ev in _host_events(tmp_path)]
    assert "engine.commit" in names
    assert not [n for n in names if n.startswith("$")]   # Python tracer


def test_names_outside_the_vocabulary_are_refused():
    with pytest.raises(ValueError):
        span("engine.nap")
    with pytest.raises(ValueError):
        scope("attention")
    called = []
    with span("engine.decode", lambda: called.append(1) or {}):
        pass
    assert not called            # arguments only while a session records


# -- scopes -------------------------------------------------------------------

def canonical_hlo(text: str) -> str:
    """Optimized HLO text without metadata: no ``metadata={...}``, no file
    and stack-frame tables, and every instruction and computation renamed
    by order of first appearance."""
    lines = text.splitlines()
    start = next(i for i, ln in enumerate(lines)
                 if i and ln.startswith(("%", "ENTRY")))
    text = "\n".join(lines[:1] + lines[start:])
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    names = {}
    return re.sub(r"%([\w.\-]+)",
                  lambda m: "%" + str(names.setdefault(m.group(1),
                                                       len(names))), text)


def _program(eng, name):
    if name == "decode":
        return eng._decode, eng._decode_args()
    if name == "chunk":
        return eng._chunk, eng._dummy_chunk_args()
    b, w = 2, eng.scheduler.buckets[0]
    route = (jnp.full((b, -(-w // eng.cfg.page_size)), eng.alloc.num_pages,
                      jnp.int32) if eng._paged
             else jnp.full((b,), eng.cfg.num_slots, jnp.int32))
    return eng._prefill, (eng.params, eng.kv, jnp.zeros((b, w), jnp.int32),
                          jnp.ones((b,), jnp.int32), route,
                          jnp.zeros((b,), jnp.float32),
                          jnp.zeros((b,), jnp.int32),
                          jnp.zeros((b,), jnp.uint32))


def _optimized(model, params, layout, name):
    eng = Engine(model, params, EngineConfig(
        num_slots=2, max_len=32, prompt_buckets=(8, 16), page_size=8,
        kv_layout=layout))
    fn, args = _program(eng, name)
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("layout", ["paged", "slots"])
@pytest.mark.parametrize("name", ["decode", "chunk", "prefill"])
def test_scopes_change_metadata_only(tiny_lm, monkeypatch, layout, name):
    _, model, params = tiny_lm
    scoped = _optimized(model, params, layout, name)
    monkeypatch.setattr(jax, "named_scope",
                        lambda _: contextlib.nullcontext())
    plain = _optimized(model, params, layout, name)
    assert canonical_hlo(scoped) == canonical_hlo(plain)
    # every op of the program lies under a scope of the vocabulary, those
    # of the layer loop's body under a block's scope
    fn = f"jit({name}_fn)/"
    names = set(re.findall(r'op_name="([^"]*)"', scoped))
    body = [n for n in names
            if n.startswith(fn + "blocks/while/body/closed_call/")]
    assert body and all(set(n.split("/")) & BLOCK_SCOPES for n in body)
    top = [n for n in names if n.startswith(fn)]
    assert top and all(n.split("/")[1] in SCOPES for n in top)
