"""Record ``serve.xplane.pb``, the small chip trace the reduction's tests
read (checked in gzipped): granite-8b's widths cut to 2 blocks, 4 slots,
two requests admitted (one batched prefill) and a few decode steps, with
the harness's spans.

    python3 bench/testdata/record.py <out.xplane.pb>      # on a TPU
    gzip -9 -c <out.xplane.pb> > bench/testdata/serve.xplane.pb.gz
"""
import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]


def main(out: str) -> None:
    import jax
    import numpy as np
    from bench import trace_reduce
    from bench.harness import cli, serve
    from bench.harness import weights as W
    from bench.harness.spans import Spans
    from repro.serving import Engine
    from repro.serving.scheduler import GenerationRequest

    cli.device_info(1)
    cfg = cli.load_json(os.path.join(ROOT, "bench/configs/granite-8b.json"))
    block = cli.load_block(ROOT, cfg["block"])
    dims = dataclasses.replace(block.Dims.from_config(cfg["model"]),
                               num_hidden_layers=2)
    ecfg = serve.engine_config(dict(cfg["engine"], num_slots=4, max_len=256,
                                    prompt_buckets=[64], num_pages=64))
    engine = Engine(serve.program_model(block, dims, cfg["arch"]),
                    W.served_params(0, block, dims, W.Recipe()), ecfg)
    rng = np.random.default_rng(0)
    reqs = [GenerationRequest(rid=i, prompt=rng.integers(0, 49152, p,
                                                         dtype=np.int32),
                              max_new_tokens=6) for i, p in enumerate((40, 60))]
    engine.warmup(reqs)
    spans = Spans(True)
    tmp = tempfile.mkdtemp()
    trace_reduce.start(tmp)
    for r in reqs:
        with spans("submit"):
            engine.submit(r)
    for _ in range(3):
        with spans("Engine.step"):
            engine.step()
    jax.block_until_ready(engine.kv)
    trace_reduce.stop()
    path = trace_reduce.find_xplane(tmp)
    shutil.copy(path, out)
    red = trace_reduce.reduce(out)
    print(json.dumps({"bytes": os.path.getsize(out),
                      "programs": red.program_count,
                      "breakdown": red.breakdown()}))


if __name__ == "__main__":
    main(sys.argv[1])
