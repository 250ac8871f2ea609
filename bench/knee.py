"""Find a serving cell's knee once, by a sweep of offered rates on the chip.

    python3 bench/knee.py --workload <name> --seed <n> --seconds <s> \
        --rates <r> <r> ...

One process, one engine: for each rate the engine is emptied, the pre-roll
fills its slots, and the cell's traffic at that rate runs for ``--seconds``.
Printed per rate: the queue depth's slope over the window (a least-squares
fit, requests per second), its mean over the last quarter, and requests
finished per second. The knee is the highest rate whose queue does not
grow; a cell's ``rate_per_s`` is then written into its traffic file as a
number. The benchmark's own runs never run this.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    import numpy as np
    from bench.harness import cli, serve
    from bench.harness import traffic as T
    from bench.harness import weights as W
    from repro.serving import Engine

    cell = cli.find_cell(ROOT, args.workload)
    cli.device_info(cell.chips)
    cli.use_compile_cache(ROOT)
    cfg, mix, dims = cell.config, cell.traffic, cell.dims
    ecfg = serve.engine_config(cfg["engine"])
    params = W.served_params(args.seed, cell.block, dims,
                             W.Recipe.from_config(cfg["weights"]))
    engine = Engine(serve.program_model(cell.block, dims, cfg["arch"]),
                    params, ecfg)
    del params
    reqs = {r: T.open_loop(dict(mix, rate_per_s=r), args.seed, args.seconds,
                           dims.vocab_size, ecfg.max_len) for r in args.rates}
    pre = T.preroll(mix, args.seed, mix["preroll_requests"], dims.vocab_size,
                    ecfg.max_len)
    engine.warmup([serve._request(r) for rs in reqs.values()
                   for r in rs + pre])
    rid = 0
    for rate in args.rates:
        for r in pre:
            engine.submit(serve._request(dataclasses.replace(r, rid=rid)))
            rid += 1
        while engine.scheduler.queue:
            engine.step()
        engine._done.clear()
        t0 = time.perf_counter()
        window, i, ts, depth, finished = reqs[rate], 0, [], [], 0
        while (now := time.perf_counter() - t0) < args.seconds:
            while i < len(window) and window[i].due_s <= now:
                engine.submit(serve._request(
                    dataclasses.replace(window[i], rid=rid)))
                rid += 1
                i += 1
            if engine.scheduler.idle:
                time.sleep(0.001)
                continue
            engine.step()
            finished += len(engine._done)
            engine._done.clear()
            ts.append(time.perf_counter() - t0)
            depth.append(len(engine.scheduler.queue))
        slope = float(np.polyfit(ts, depth, 1)[0]) if len(ts) > 2 else 0.0
        tail = depth[len(depth) * 3 // 4:] or [0]
        print(json.dumps({"rate_per_s": rate, "due": len(window),
                          "queue_slope_per_s": slope,
                          "queue_last_quarter": float(np.mean(tail)),
                          "finished_per_s": finished / args.seconds,
                          "steps": len(ts)}), flush=True)
        for slot in list(engine.scheduler.active_slots()):
            engine.cancel(engine.scheduler.slots[slot].request.rid)
        for item in list(engine.scheduler.queue):
            engine.cancel(getattr(item, "request", item).rid)
        engine.run()


if __name__ == "__main__":
    main()
