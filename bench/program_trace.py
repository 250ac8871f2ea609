"""From a profiler trace (``.xplane.pb``) to the program's own names: each
device op put down to the step program's layer scope, each device-idle gap
put down to the ``Engine.step`` phase the host was in.

The vocabulary is the program's (``repro.obs.spans``): ``jax.named_scope``
names in the step programs, ``engine.*`` host spans in ``Engine.step``. A
program without them (an older checkout) still reduces: its named ops then
read ``unscoped.other`` and it has no phases.

- **Ops to instructions.** The trace's ``/host:metadata`` plane carries each
  program's optimized HLO as an event-metadata stat named ``Hlo Proto``.
  It is read by a protobuf wire-format walk (field numbers from XLA's
  ``xplane.proto`` and ``hlo.proto``; the other planes' bytes are skipped)
  into instruction -> (opcode, op_name, result shape, computation). A
  device ``XLA Ops`` event is named by its instruction's HLO text; its
  program is the ``XLA Modules`` event it falls in.
- **Buckets.** Every ``XLA Ops`` event of a program but the container ops
  (``while``, ``conditional``, ``call``) lands in exactly one bucket, so the
  buckets partition the program's op time:

  - ``scan.kv`` / ``scan.weights``: the layer loop's own slices and updates
    (op_name ``[blocks/]while/body/{dynamic_slice,squeeze,
    dynamic_update_slice}``) and the ops XLA inserted into the loop body
    (no op_name); pool-shaped ones are ``scan.kv``;
  - ``unscoped.kv`` / ``unscoped.other``: other ops with no op_name,
    pool-shaped or not;
  - the innermost vocabulary scope of the op_name; ``unscoped.other`` when
    the op_name holds none;
  - ``unmatched``: an event whose instruction is not in the trace's HLO.

  A result shape is pool-shaped when it ends in the KV pool's per-layer
  shape (pages, page size, KV heads, head size).
- **Phases.** Device-idle time in the window (no op running on a device) is
  cut at the host spans' edges; each piece goes to the innermost
  ``engine.*`` span around it (``unphased`` when none), and each gap is
  named by the phase that holds most of it. Each ``engine.*`` span
  starting in the window is counted and its arguments summed.

``read`` caches its result per trace, pool shape and window: the readers of
one run share one reduction.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

from bench import trace_reduce
from bench.harness.cli import BenchError

try:                        # the program's vocabulary; absent before it
    from repro.obs.spans import SCOPES
except ImportError:         # pragma: no cover - older program
    SCOPES = ()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCAN_OPS = frozenset(("dynamic_slice", "squeeze", "dynamic_update_slice"))
CONTAINERS = frozenset(trace_reduce.CONTAINERS)
KV_BUCKETS = ("scan.kv", "unscoped.kv")
DEQUANT_WRAPPERS = frozenset(("jit(dequant_matmul)",
                              "vmap(jit(dequant_matmul))"))
ENGINE = "engine."
_INSTR = re.compile(r"^%?([^\s=]+)")


# -- protobuf wire format -----------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo: int = 0, hi: Optional[int] = None) -> Iterator[tuple]:
    """(field, wire type, value) of one message in ``buf[lo:hi]``: an int
    for varint and fixed fields, a (start, end) span for length-delimited
    ones."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        field, wt = key >> 3, key & 7
        if wt == 0:
            value, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wt == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wt == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield field, wt, value


def _str(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _ints(buf, wt: int, value) -> List[int]:
    """A repeated integer field's values, packed or not."""
    if wt == 0:
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        v, i = _varint(buf, i)
        out.append(v)
    return out


@dataclasses.dataclass(frozen=True)
class Instr:
    opcode: str
    op_name: str
    shape: Tuple[int, ...]      # result dimensions (empty for a tuple)
    computation: int            # id of the computation holding it


@dataclasses.dataclass
class Hlo:
    """One program's instructions by name, and the computations its layer
    loop runs (body and condition)."""
    instrs: Dict[str, Instr]
    loop_computations: frozenset


def _path(op_name: str) -> List[str]:
    """op_name components below the program's ``jit(<fn>)``."""
    parts = op_name.split("/")
    return parts[1:] if parts and parts[0].startswith("jit(") else parts


def _is_layer_loop(op_name: str) -> bool:
    p = _path(op_name)
    return p == ["while"] or p == ["blocks", "while"]


def parse_hlo(buf, span) -> Hlo:
    """HloProto (hlo_module = 1) -> :class:`Hlo`."""
    instrs: Dict[str, Instr] = {}
    loops: List[List[int]] = []
    for f, _, mod in _fields(buf, *span):
        if f != 1:
            continue
        for f2, _, comp in _fields(buf, *mod):
            if f2 != 3:                          # computations
                continue
            raw, cid = [], 0
            for f3, wt3, v3 in _fields(buf, *comp):
                if f3 == 2:                      # instructions
                    raw.append(v3)
                elif f3 == 5:                    # id
                    cid = v3
            for ispan in raw:
                name = opcode = op_name = ""
                shape: List[int] = []
                called: List[int] = []
                for f4, wt4, v4 in _fields(buf, *ispan):
                    if f4 == 1:
                        name = _str(buf, v4)
                    elif f4 == 2:
                        opcode = _str(buf, v4)
                    elif f4 == 3:                # ShapeProto
                        for f5, wt5, v5 in _fields(buf, *v4):
                            if f5 == 3:          # dimensions
                                shape += _ints(buf, wt5, v5)
                    elif f4 == 7:                # OpMetadata
                        for f5, _, v5 in _fields(buf, *v4):
                            if f5 == 2:          # op_name
                                op_name = _str(buf, v5)
                    elif f4 == 38:               # called_computation_ids
                        called += _ints(buf, wt4, v4)
                instrs[name] = Instr(opcode, op_name, tuple(shape), cid)
                if opcode == "while" and _is_layer_loop(op_name):
                    loops.append(called)
    return Hlo(instrs, frozenset(c for cs in loops for c in cs))


def hlo_protos(data) -> Dict[str, tuple]:
    """Program name (``jit_decode_fn(<fingerprint>)``, as the device's
    ``XLA Modules`` events name it) -> the (start, end) of its HloProto
    bytes in ``data``, from the ``/host:metadata`` plane."""
    out = {}
    for f, _, plane in _fields(data):
        if f != 1:                               # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for f2, _, v2 in _fields(data, *plane):
            if f2 == 2:
                name = _str(data, v2)
            elif name and name != "/host:metadata":
                break                            # skip the plane's bytes
            elif f2 == 4:                        # event_metadata map entry
                metas.append(v2)
            elif f2 == 5:                        # stat_metadata map entry
                for f3, _, v3 in _fields(data, *v2):
                    if f3 == 2:
                        sid = sname = None
                        for f4, _, v4 in _fields(data, *v3):
                            if f4 == 1:
                                sid = v4
                            elif f4 == 2:
                                sname = _str(data, v4)
                        stat_names[sid] = sname
        if name != "/host:metadata":
            continue
        for entry in metas:
            for f3, _, em in _fields(data, *entry):
                if f3 != 2:
                    continue
                ename, stats = "", []
                for f4, _, v4 in _fields(data, *em):
                    if f4 == 2:
                        ename = _str(data, v4)
                    elif f4 == 5:
                        stats.append(v4)
                for st in stats:
                    sid, blob = None, None
                    for f5, _, v5 in _fields(data, *st):
                        if f5 == 1:
                            sid = v5
                        elif f5 == 6:            # bytes_value
                            blob = v5
                    if blob is not None and stat_names.get(sid) == "Hlo Proto":
                        out[ename] = blob
    return out


# -- buckets ------------------------------------------------------------------

def _pool_shaped(shape: Tuple[int, ...], pool) -> bool:
    return bool(pool) and len(shape) >= len(pool) and \
        shape[-len(pool):] == tuple(pool)


def bucket(ins: Optional[Instr], pool, loops=frozenset()) -> str:
    """The bucket of one op (see the module docstring)."""
    if ins is None:
        return "unmatched"
    kv = _pool_shaped(ins.shape, pool)
    if not ins.op_name:
        if ins.computation in loops:
            return "scan.kv" if kv else "scan.weights"
        return "unscoped.kv" if kv else "unscoped.other"
    p = _path(ins.op_name)
    i = 1 if p[:1] == ["blocks"] else 0
    if (len(p) == i + 3 and p[i:i + 2] == ["while", "body"]
            and set(p[-1].split(";")) <= SCAN_OPS):
        return "scan.kv" if kv else "scan.weights"
    for part in reversed(p):
        if part in SCOPES:
            return part
    return "unscoped.other"


def _weight_gather(ins: Optional[Instr]) -> bool:
    """An op of the dequant-matmul wrapper that is not its Pallas kernel:
    the scale/zero gathers and the activation split, of one linear or of
    stacked experts (the wrapper under ``vmap``)."""
    return (ins is not None and ins.opcode != "custom-call"
            and not DEQUANT_WRAPPERS.isdisjoint(ins.op_name.split("/")))


# -- the reduction ------------------------------------------------------------

@dataclasses.dataclass
class ProgramTrace:
    path: str
    window: Tuple[int, int]                        # ns
    devices: int                                   # planes with XLA Ops
    program_ns: Dict[str, float]                   # program -> op time
    buckets: Dict[str, Dict[str, float]]           # program -> bucket -> ns
    weight_gather_ns: Dict[str, float]             # program -> ns
    idle_ns: float                                 # device-idle, in window
    phases: Dict[str, float]                       # engine span -> idle ns
    idle_in_step_ns: float                         # idle under Engine.step
    unphased_in_step_ns: float                     # ... and no engine span
    gaps: List[Tuple[int, int, str]]               # (start, end, phase)
    span_count: Dict[str, int]
    span_args: Dict[str, Dict[str, float]]

    def share(self, program: str, buckets, extra_ns: float = 0.0):
        """Percent of ``program``'s op time in ``buckets`` (+ extra)."""
        total = self.program_ns.get(program, 0.0)
        if total <= 0:
            return None
        got = sum(self.buckets[program].get(b, 0.0) for b in buckets)
        return 100.0 * (got + extra_ns) / total


def newest(root: str = ROOT) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``<root>/.bench_trace``."""
    paths = glob.glob(os.path.join(root, ".bench_trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def pool_shape(block, dims, engine_cfg: dict) -> Optional[tuple]:
    """Per-layer KV pool shape of a paged engine configuration: its pages
    of the block's KV heads and head size. There is one pool, so the
    block's attention layers all have one page shape; a block whose layers
    differ there is an error."""
    if engine_cfg.get("kv_layout") != "paged":
        return None
    pages = {(kv_heads, head_dim) for _, kv_heads, head_dim, _
             in block.decode_attention(dims)}
    if len(pages) != 1:
        raise BenchError(f"the block's attention layers have (KV heads, "
                         f"head size) {sorted(pages)}; the paged pool "
                         f"holds one page shape")
    (kv_heads, head_dim), = pages
    return (int(engine_cfg["num_pages"]), int(engine_cfg["page_size"]),
            int(kv_heads), int(head_dim))


def for_context(ctx) -> Optional[ProgramTrace]:
    """This run's reduction for a serving context: its newest trace, its
    pool, the harness's traced window."""
    tr = getattr(ctx, "trace", None)
    path = newest()
    if tr is None or path is None:
        return None
    return read(path, pool_shape(ctx.block, ctx.dims, ctx.engine_cfg),
                tuple(tr.window))


def read(path: str, pool, window) -> ProgramTrace:
    """The reduction of the trace at ``path`` over ``window`` (ns), with
    ``pool`` the KV pool's per-layer shape (None: nothing is pool-shaped)."""
    return _read(path, os.path.getmtime(path), tuple(pool) if pool else None,
                 tuple(window))


@functools.lru_cache(maxsize=4)
def _read(path: str, mtime: float, pool, window) -> ProgramTrace:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    protos = hlo_protos(data)
    pd = ProfileData.from_serialized_xspace(data)
    hlos: Dict[str, Hlo] = {}
    host: List[tuple] = []                     # (start, end, name, stats)
    harness: List[Tuple[int, int]] = []        # the harness's Engine.step
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    n = ev.name
                    if n.startswith(ENGINE):
                        host.append((int(ev.start_ns), int(ev.end_ns), n,
                                     dict(ev.stats)))
                    elif n == "Engine.step":
                        harness.append((int(ev.start_ns), int(ev.end_ns)))
    program_ns = collections.Counter()
    buckets: Dict[str, collections.Counter] = collections.defaultdict(
        collections.Counter)
    gather_ns = collections.Counter()
    busy: List[List[Tuple[int, int]]] = []
    for d in devices:
        mods = sorted((int(ev.start_ns), int(ev.end_ns), ev.name)
                      for ev in (d["XLA Modules"].events
                                 if "XLA Modules" in d else ()))
        starts = [m[0] for m in mods]
        ops = []
        for ev in d["XLA Ops"].events:
            s, e = int(ev.start_ns), int(ev.end_ns)
            if e <= window[0] or s >= window[1]:
                continue
            ops.append((s, e))
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or mods[i][1] < s:
                continue
            full = mods[i][2]
            if full not in hlos and full in protos:
                hlos[full] = parse_hlo(data, protos[full])
            hlo = hlos.get(full)
            m = _INSTR.match(ev.name)
            ins = hlo.instrs.get(m.group(1)) if hlo and m else None
            if (ins.opcode if ins else trace_reduce.base_name(ev.name)) \
                    in CONTAINERS:
                continue
            prog = trace_reduce.module_name(full)
            ns = float(e - s)
            program_ns[prog] += ns
            buckets[prog][bucket(ins, pool, hlo.loop_computations
                                 if hlo else frozenset())] += ns
            if _weight_gather(ins):
                gather_ns[prog] += ns
        busy.append(trace_reduce.union(ops))

    idle, phases, in_step, unphased = 0.0, collections.Counter(), 0.0, 0.0
    gaps: List[Tuple[int, int, str]] = []
    count = collections.Counter()
    args: Dict[str, collections.Counter] = collections.defaultdict(
        collections.Counter)
    host.sort()
    longest = max((e - s for s, e, _, _ in host), default=0)
    for s, e, n, st in host:
        if window[0] <= s < window[1]:
            count[n] += 1
            for k, v in st.items():
                if isinstance(v, (int, float)):
                    args[n][k] += v
    harness_u = trace_reduce.union(harness)
    for b in busy:
        for gs, ge in _gaps(trace_reduce.clip(b, *window), window):
            idle += ge - gs
            held = collections.Counter()
            for ps, pe, name in _pieces(gs, ge, host, longest):
                phases[name] += pe - ps
                held[name] += pe - ps
                ov = _overlap(ps, pe, harness_u)
                in_step += ov
                if name == "unphased":
                    unphased += ov
            gaps.append((gs, ge, held.most_common(1)[0][0]))
    n_dev = max(len(busy), 1)
    idle /= n_dev
    in_step /= n_dev
    unphased /= n_dev
    return ProgramTrace(path, window, len(devices), dict(program_ns),
                        {k: dict(v) for k, v in buckets.items()},
                        dict(gather_ns), idle,
                        {k: v / n_dev for k, v in phases.items()}, in_step,
                        unphased, sorted(gaps), dict(count),
                        {k: dict(v) for k, v in args.items()})


def _gaps(busy, window) -> Iterator[Tuple[int, int]]:
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    for gs, ge in zip(edges[0::2], edges[1::2]):
        if ge > gs:
            yield gs, ge


def _overlap(s: int, e: int, intervals) -> int:
    """Length of [s, e) covered by sorted, disjoint ``intervals``."""
    i = max(bisect.bisect_right(intervals, (s,)) - 1, 0)
    total = 0
    while i < len(intervals) and intervals[i][0] < e:
        total += max(0, min(e, intervals[i][1]) - max(s, intervals[i][0]))
        i += 1
    return total


def _pieces(s: int, e: int, host, longest: int
            ) -> Iterator[Tuple[int, int, str]]:
    """[s, e) cut at the edges of the host spans (sorted by start, none
    longer than ``longest``) that overlap it, each piece named by the
    innermost span around it: the one that started last (ended first on a
    tie)."""
    lo = bisect.bisect_left(host, (s - longest,))
    hi = bisect.bisect_left(host, (e,))
    around = [h for h in host[lo:hi] if h[1] > s]
    cuts = sorted({s, e} | {x for h in around for x in h[:2] if s < x < e})
    for a, b in zip(cuts, cuts[1:]):
        inner = max((h for h in around if h[0] <= a and h[1] >= b),
                    key=lambda h: (h[0], -h[1]), default=None)
        yield a, b, inner[2] if inner else "unphased"
