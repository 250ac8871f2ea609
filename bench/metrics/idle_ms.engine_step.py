"""Engine host: device-idle milliseconds per decode step that the host
spends in ``Engine.step``'s own phases (every ``engine.*`` span but
``engine.decode.wait``, the step's one transfer), over the traced window;
bench/program_trace.py. None when the program writes no ``engine.*``
spans."""
from bench import program_trace


def read(ctx, peaks):
    pt = program_trace.for_context(ctx)
    if pt is None or not pt.devices or not pt.span_count.get("engine.decode"):
        return None
    idle = sum(ns for name, ns in pt.phases.items()
               if name.startswith("engine.") and name != "engine.decode.wait")
    return idle * 1e-6 / pt.span_count["engine.decode"]
