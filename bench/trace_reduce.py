"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
readers use. The same reduction for every PR, kept with the benchmark.

Read with ``jax.profiler.ProfileData``. On a TPU the device planes are
``/device:TPU:<n>``; their ``XLA Modules`` line holds one event per program
execution (named after the jitted function) and their ``XLA Ops`` line one
event per operation, a Pallas kernel under the name it was given (with
XLA's ``.<n>`` suffix). The host plane carries the harness's
``TraceAnnotation`` spans on the same clock.

- busy: the union of the operation intervals on a device, clipped to the
  traced window; ``busy_s`` is its length averaged over the devices;
- ``window_s``: from the first harness span's start to the last one's end;
- time per program, and per (program, operation): sums of event
  durations; each Pallas kernel call's shapes, read from its HLO text;
- idle gaps: the holes in the busy union inside the window, each named by
  the harness span it overlaps most (``untraced`` when none).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

HARNESS_SPANS = ("Engine.step", "submit", "idle")
CONTAINERS = ("while", "conditional", "call")   # ops that hold other ops
KERNELS = ("dequant_matmul", "flash_decode", "awp_pgd", "kv_dequant",
           "quant_proj", "topk_mask")              # the Pallas kernels' names
_OP = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?(?:\s*=|$)")
_MODULE = re.compile(r"^(jit_)?([A-Za-z0-9_]+)")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[([0-9,]*)\]")


def base_name(op: str) -> str:
    """``%dequant_matmul.62 = f32[16,4096]{...} custom-call(...)`` (a TPU
    op event's name is its HLO text) or ``dequant_matmul.62`` ->
    ``dequant_matmul``."""
    m = _OP.match(op)
    return m.group(1) if m else op


def shapes(op: str) -> Tuple[Tuple[int, ...], ...]:
    """The array shapes in an op event's HLO text: the result first, then
    the operands, as written."""
    return tuple(tuple(int(x) for x in m.group(1).split(",") if x)
                 for m in _SHAPE.finditer(op.split("{", 1)[0] + " " +
                                          op.split("(", 1)[-1]))


def module_name(name: str) -> str:
    """``jit_decode_fn(1234)`` -> ``decode_fn``."""
    m = _MODULE.match(name)
    return m.group(2) if m else name


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Reduction:
    window: Tuple[int, int]                        # ns
    busy_ns: float                                 # mean over devices
    devices: int
    program_ns: Dict[str, float]                   # module -> ns
    program_count: Dict[str, int]
    in_program: Dict[Tuple[str, str], float]       # (module, op) -> ns
    in_program_count: Dict[Tuple[str, str], int]
    calls: Dict[Tuple[str, str], Dict[tuple, int]]  # (module, kernel) ->
    #                                                {shapes: calls}
    gaps: List[Tuple[str, float]]                  # (span, ns), longest first

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def kernel_s(self, kernel: str, program: str) -> float:
        return self.in_program.get((program, kernel), 0.0) * 1e-9

    def program_s(self, program: str) -> float:
        return self.program_ns.get(program, 0.0) * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(((f"{m}/{k}", ns * 1e-9)
                      for (m, k), ns in self.in_program.items()
                      if k not in CONTAINERS),
                     key=lambda x: -x[1])[:top]
        by_span = collections.defaultdict(lambda: [0.0, 0])
        for name, ns in self.gaps:
            by_span[name][0] += ns * 1e-9
            by_span[name][1] += 1
        gaps = sorted(([f"{n} ({c} gaps)", s] for n, (s, c)
                       in by_span.items()), key=lambda x: -x[1])
        longest = [[f"{n} (longest)", ns * 1e-9] for n, ns in self.gaps]
        return {"device_ops": [list(x) for x in ops],
                "idle_gaps": (gaps + longest)[:top]}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce(path: str, spans: Iterable[str] = HARNESS_SPANS) -> Reduction:
    from jax.profiler import ProfileData
    spans = tuple(spans)
    pd = ProfileData.from_file(path)
    host: List[Tuple[int, int, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in spans:
                        host.append((int(ev.start_ns), int(ev.end_ns),
                                     ev.name))
    if not devices:
        raise ValueError(f"{path}: no device plane with XLA Ops")
    host.sort()
    if host:
        window = (min(s for s, _, _ in host), max(e for _, e, _ in host))
    else:
        starts = [int(ev.start_ns) for d in devices
                  for ev in d["XLA Ops"].events]
        ends = [int(ev.end_ns) for d in devices
                for ev in d["XLA Ops"].events]
        window = (min(starts), max(ends))
    program_ns = collections.Counter()
    program_count = collections.Counter()
    in_program = collections.Counter()
    in_program_count = collections.Counter()
    calls = collections.defaultdict(collections.Counter)
    busy_total = 0.0
    gaps: List[Tuple[str, float]] = []
    for d in devices:
        mods = []
        for ev in d.get("XLA Modules", None).events if "XLA Modules" in d \
                else []:
            s, e = int(ev.start_ns), int(ev.end_ns)
            if e <= window[0] or s >= window[1]:
                continue
            name = module_name(ev.name)
            mods.append((s, e, name))
            program_ns[name] += e - s
            program_count[name] += 1
        mods.sort()
        starts = [m[0] for m in mods]
        ops = []
        for ev in d["XLA Ops"].events:
            s, e = int(ev.start_ns), int(ev.end_ns)
            if e <= window[0] or s >= window[1]:
                continue
            ops.append((s, e))
            k = base_name(ev.name)
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
            in_program[(mod, k)] += e - s
            in_program_count[(mod, k)] += 1
            if k in KERNELS:
                calls[(mod, k)][shapes(ev.name)] += 1
        busy = clip(union(ops), *window)
        busy_total += sum(e - s for s, e in busy)
        edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((_attribute(gs, ge, host), float(ge - gs)))
    gaps.sort(key=lambda g: -g[1])
    return Reduction(window, busy_total / len(devices), len(devices),
                     dict(program_ns),
                     dict(program_count), dict(in_program),
                     dict(in_program_count),
                     {k: dict(v) for k, v in calls.items()}, gaps)


def _attribute(s: int, e: int, host) -> str:
    """The harness span (sorted by start) overlapping [s, e) the most."""
    best, name = 0, "untraced"
    i = bisect.bisect_right(host, (e,)) - 1
    while i >= 0:
        hs, he, n = host[i]
        ov = min(e, he) - max(s, hs)
        if ov > best:
            best, name = ov, n
        if he < s and hs < s - 10_000_000_000:   # spans end within 10 s
            break
        i -= 1
    return name


def start(trace_dir: str) -> None:
    """Start a device trace without the Python tracer (it would bloat the
    trace and slow the host); harness spans are kept."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def reduce_dir(trace_dir: str, spans: Iterable[str] = HARNESS_SPANS):
    return reduce(find_xplane(trace_dir), spans)
