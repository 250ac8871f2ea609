"""The program-name reduction (``bench/program_trace.py``): the protobuf
walk, the buckets and the phases, and the whole reduction on two small
traces recorded on the chip with ``bench/testdata/record.py`` (2 blocks at
granite-8b's widths, 64 pages): ``serve.xplane.pb.gz``, from a program
without scopes or engine spans, and ``serve_scoped.xplane.pb.gz``, from
one with them."""
import gzip
import json
import os
import re
import shutil
import types

import pytest

from benchutil import ROOT

from bench import program_trace as P
from bench import trace_reduce
from bench.harness import cli

POOL = (64, 16, 8, 128)            # record.py's pool: 64 pages of 16
TRACES = {"plain": "serve.xplane.pb.gz", "scoped": "serve_scoped.xplane.pb.gz"}


def unpacked(tmp_dir, which: str) -> str:
    path = os.path.join(str(tmp_dir), TRACES[which][:-3])
    with gzip.open(os.path.join(ROOT, "bench", "testdata", TRACES[which]),
                   "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


def reduced(path):
    """The reduction as the readers make it: over the harness's window."""
    return P.read(path, POOL, trace_reduce.reduce(path).window)


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    return reduced(unpacked(tmp_path_factory.mktemp("plain"), "plain"))


@pytest.fixture(scope="module")
def scoped_path(tmp_path_factory):
    return unpacked(tmp_path_factory.mktemp("scoped"), "scoped")


@pytest.fixture(scope="module")
def scoped(scoped_path):
    return reduced(scoped_path)


# -- pieces -------------------------------------------------------------------

def test_wire_format_walk():
    # field 1 varint 150, field 2 bytes "hi", field 3 packed [1, 300]
    msg = bytes([0x08, 0x96, 0x01, 0x12, 0x02]) + b"hi" + \
        bytes([0x1A, 0x03, 0x01, 0xAC, 0x02])
    got = list(P._fields(msg))
    assert got[0] == (1, 0, 150)
    assert got[1][:2] == (2, 2) and P._str(msg, got[1][2]) == "hi"
    assert P._ints(msg, 2, got[2][2]) == [1, 300]


def ins(op_name, shape=(4, 4), comp=0, opcode="fusion"):
    return P.Instr(opcode, op_name, shape, comp)


@pytest.mark.parametrize("op_name,shape,comp,want", [
    ("jit(decode_fn)/blocks/while/body/squeeze", (4096, 2048), 0,
     "scan.weights"),
    ("jit(decode_fn)/while/body/dynamic_update_slice", (2,) + POOL, 0,
     "scan.kv"),
    ("jit(decode_fn)/blocks/while/body/add", (), 0, "blocks"),
    ("jit(decode_fn)/blocks/while/body/closed_call/attn/jit(decode_attn_"
     "paged)/flash_decode/while/body/dynamic_slice", (4,), 0, "attn"),
    ("jit(decode_fn)/blocks/while/body/closed_call/mlp/jit(dequant_matmul)"
     "/gather", (7168, 4), 0, "mlp"),
    ("jit(decode_fn)/sample/vmap()/top_k", (4,), 0, "sample"),
    ("jit(decode_fn)/dot_general", (4, 49152), 0, "unscoped.other"),
    ("", (2,) + POOL, 0, "unscoped.kv"),
    ("", (1,) + POOL, 7, "scan.kv"),
    ("", (1, 14336, 32), 7, "scan.weights"),
    ("", (4,), 0, "unscoped.other"),
])
def test_buckets(op_name, shape, comp, want):
    assert P.bucket(ins(op_name, shape, comp), POOL, frozenset({7})) == want


def test_unmatched_and_weight_gathers():
    assert P.bucket(None, POOL) == "unmatched"
    wrap = "jit(decode_fn)/blocks/while/body/closed_call/mlp/" \
        "jit(dequant_matmul)/"
    assert P._weight_gather(ins(wrap + "gather"))
    assert not P._weight_gather(ins(wrap + "dequant_matmul/pallas_call",
                                    opcode="custom-call"))
    assert not P._weight_gather(ins("jit(decode_fn)/head/dot_general"))


def test_idle_pieces_go_to_the_innermost_span():
    host = sorted([(0, 100, "engine.step", {}), (0, 30, "engine.expire", {}),
                   (30, 60, "engine.admit", {}),
                   (40, 50, "engine.prefill", {}),
                   (60, 90, "engine.decode", {})])
    got = list(P._pieces(20, 110, host, 100))
    assert got == [(20, 30, "engine.expire"), (30, 40, "engine.admit"),
                   (40, 50, "engine.prefill"), (50, 60, "engine.admit"),
                   (60, 90, "engine.decode"), (90, 100, "engine.step"),
                   (100, 110, "unphased")]


def test_overlap_with_sorted_intervals():
    assert P._overlap(5, 25, [(0, 10), (12, 14), (20, 40)]) == 5 + 2 + 5
    assert P._overlap(50, 60, [(0, 10)]) == 0


# -- the trace of a program without names -------------------------------------

def _events(path):
    """(program, instruction name, event name) of every XLA Ops event."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        mods = sorted((ev.start_ns, ev.end_ns, ev.name)
                      for ev in lines["XLA Modules"].events)
        for ev in lines["XLA Ops"].events:
            mod = next(m[2] for m in mods if m[0] <= ev.start_ns <= m[1])
            out.append((mod, re.match(r"%?(\S+?) =", ev.name).group(1),
                        ev.name))
    return out


def test_every_op_of_the_step_programs_finds_its_instruction(plain):
    with open(plain.path, "rb") as f:
        data = f.read()
    protos = P.hlo_protos(data)
    assert {trace_reduce.module_name(m) for m in protos} == {"decode_fn",
                                                             "prefill_fn"}
    hlos = {m: P.parse_hlo(data, span) for m, span in protos.items()}
    events = _events(plain.path)
    assert len(events) > 1000
    for mod, name, _ in events:
        assert name in hlos[mod].instrs, (mod, name)
    assert "unmatched" not in plain.buckets["decode_fn"]
    assert "unmatched" not in plain.buckets["prefill_fn"]


def test_buckets_partition_program_time(plain):
    red = trace_reduce.reduce(plain.path)
    for prog in ("decode_fn", "prefill_fn"):
        assert sum(plain.buckets[prog].values()) == pytest.approx(
            plain.program_ns[prog], rel=1e-12)
        # op time is the program's device time, less the gaps between ops
        assert plain.program_ns[prog] == pytest.approx(
            red.program_ns[prog], rel=0.005)


def test_weight_slices_and_the_pool_copy(plain):
    with open(plain.path, "rb") as f:
        data = f.read()
    (mod, span), = [(m, s) for m, s in P.hlo_protos(data).items()
                    if "decode_fn" in m]
    hlo = P.parse_hlo(data, span)
    seen = set()
    for m, name, text in _events(plain.path):
        if m != mod:
            continue
        i = hlo.instrs[name]
        b = P.bucket(i, POOL, hlo.loop_computations)
        if i.op_name.endswith("while/body/squeeze") and i.shape[0] > 1000:
            assert re.match(r"%\S+ = u8\[", text)          # packed
            assert b == "scan.weights"
            seen.add("weights")
        if i.opcode == "copy" and i.shape == (2,) + POOL and not i.op_name:
            assert b == "unscoped.kv"
            seen.add("pool")
    assert seen == {"weights", "pool"}
    assert plain.buckets["decode_fn"]["scan.weights"] > 0
    # no scopes, no engine spans: named ops are unscoped, there are no
    # phases, and the idle-time reader has nothing to read
    assert plain.span_count == {} and set(plain.phases) <= {"unphased"}


# -- the trace of a program with names ----------------------------------------

def _context(path, steps=()):
    """What a serving cell's readers get, at record.py's sizes."""
    conf = json.load(open(os.path.join(ROOT, "bench/configs/granite-8b.json")))
    block = cli.load_block(ROOT, conf["block"])
    dims = block.Dims.from_config(dict(conf["model"], num_hidden_layers=2))
    ctx = types.SimpleNamespace(
        block=block, dims=dims, trace=trace_reduce.reduce(path),
        engine_cfg=dict(conf["engine"], num_slots=4, max_len=256,
                        prompt_buckets=[64], num_pages=64))
    if steps:
        ctx.steps = list(steps)
    return ctx


@pytest.mark.parametrize("metric", ["share.kv_carry.decode",
                                    "share.weight_fetch.decode",
                                    "idle_ms.engine_step"])
def test_each_new_reader_reads_the_scoped_trace(scoped_path, monkeypatch,
                                                metric):
    monkeypatch.setattr(P, "newest", lambda root=P.ROOT: scoped_path)
    value = cli.reader(ROOT, metric)(_context(scoped_path), {})
    assert value is not None and value > 0
    if metric.startswith("share."):
        assert value < 100


# The readers that count through the block module read what they read
# before the block moved into bench/harness/blocks/: the same floats, to
# the bit, on the same trace and steps. The steps are the trace's own three
# decode steps, two rows each (its engine.decode spans: 6 rows, 312 live
# tokens in all), each row one token further on at each step.
@pytest.mark.parametrize("metric,value", [
    ("mfu.decode", 0.2523665984343416),
    ("roofline.flash_decode", 5.898294205323746)])
def test_block_readers_read_as_before_on_the_scoped_trace(scoped_path,
                                                          metric, value):
    from bench.harness.serve import StepRecord
    steps = [StepRecord((50, 52)), StepRecord((51, 53)),
             StepRecord((52, 54))]
    peaks = cli.peaks_for(ROOT, "TPU v5 lite")
    assert cli.reader(ROOT, metric)(_context(scoped_path, steps),
                                    peaks) == value


def test_pool_shape_is_the_blocks_kv_page(scoped_path):
    ctx = _context(scoped_path)
    assert P.pool_shape(ctx.block, ctx.dims, ctx.engine_cfg) == POOL
    assert P.pool_shape(ctx.block, ctx.dims,
                        dict(ctx.engine_cfg, kv_layout="slot")) is None


def test_every_gap_in_an_engine_step_names_an_engine_phase(scoped):
    steps = _harness(scoped.path)
    inside = [g for g in scoped.gaps
              if any(s <= g[0] and g[1] <= e for s, e in steps)]
    assert inside and all(g[2].startswith("engine.") for g in inside)
    assert scoped.unphased_in_step_ns <= 0.1 * scoped.idle_in_step_ns


def _harness(path):
    from jax.profiler import ProfileData
    return sorted((ev.start_ns, ev.end_ns)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name == "Engine.step")


def test_scoped_decode_ops_are_named(scoped):
    b = scoped.buckets["decode_fn"]
    total = scoped.program_ns["decode_fn"]
    assert sum(b.values()) == pytest.approx(total, rel=1e-12)
    assert b.get("unscoped.other", 0.0) + b.get("unmatched", 0.0) \
        <= 0.03 * total
    assert {"attn_in", "attn", "mlp", "head", "sample"} <= set(b)
    assert scoped.span_count["engine.decode"] == 3
