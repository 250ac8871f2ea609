"""Kernel microbenchmarks: Pallas (interpret on CPU; native on TPU) vs the
jnp oracle, with FLOP-derived throughput. On this CPU container the µs are
indicative only — the structural payload is the HLO/roofline work in
benchmarks/roofline_report.py. Emits machine-readable
``results/BENCH_kernels.json`` alongside the stdout CSV."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit_json, timed
from repro.kernels import ops
from repro.quant.qtensor import QTensor
from repro.serving.kv_cache import kv_quantize

_BACKEND = jax.default_backend()
_INTERPRET = ops._interpret()


def _row(name, us, derived_value, derived_unit):
    # every row carries where it ran: Pallas kernels execute in interpret
    # mode off-TPU, so CPU µs are comparable only to other interpret rows
    return {"kernel": name, "us_per_call": round(us, 1),
            "derived_value": round(derived_value, 1),
            "derived_unit": derived_unit,
            "backend": _BACKEND, "interpret": _INTERPRET}


def run():
    rng = np.random.default_rng(0)
    rows = []
    m, k = 256, 512
    w = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    th = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(k, k)), jnp.float32)

    flops = 2 * m * k * k
    for use in (True, False):
        us = timed(lambda: ops.awp_pgd_step(w, th, c, 0.1, use_pallas=use))
        rows.append(_row(f"awp_pgd_step[{'pallas' if use else 'jnp'}]", us,
                         flops / us / 1e3, "GFLOP/s"))

    # batched (shape-bucket) form: B small problems as one program
    b, mb, kb = 8, 64, 128
    w_b = jnp.asarray(rng.normal(size=(b, mb, kb)), jnp.float32)
    th_b = jnp.asarray(rng.normal(size=(b, mb, kb)), jnp.float32)
    c_b = jnp.asarray(rng.normal(size=(b, kb, kb)), jnp.float32)
    eta_b = jnp.full((b,), 0.1, jnp.float32)
    bflops = 2 * b * mb * kb * kb
    for use in (True, False):
        us = timed(lambda: ops.awp_pgd_step(w_b, th_b, c_b, eta_b,
                                            use_pallas=use))
        rows.append(_row(
            f"awp_pgd_step_batched[{'pallas' if use else 'jnp'}]", us,
            bflops / us / 1e3, "GFLOP/s"))

    for use in (True, False):
        us = timed(lambda: ops.topk_row(w, k // 2, use_pallas=use))
        rows.append(_row(f"topk_row[{'pallas' if use else 'jnp'}]", us,
                         m * k / us, "elem/us"))

    for use in (True, False):
        us = timed(lambda: ops.quant_project(w, 4, 128, use_pallas=use))
        rows.append(_row(f"quant_proj[{'pallas' if use else 'jnp'}]", us,
                         m * k / us, "elem/us"))

    qt = QTensor.from_dense(w, 4, 128)
    x = jnp.asarray(rng.normal(size=(64, k)), jnp.float32)
    for use in (True, False):
        us = timed(lambda: ops.dequant_matmul(x, qt.packed, qt.scale, qt.zero,
                                              128, use_pallas=use))
        rows.append(_row(f"dequant_matmul[{'pallas' if use else 'jnp'}]", us,
                         2 * 64 * m * k / us / 1e3, "GFLOP/s"))

    rows += _decode_attn_rows(rng)
    return rows


def _decode_attn_rows(rng):
    """Fused flash-decode vs reference gather-then-attend over paged dense
    and INT8 pools at two cache depths. Derived metric is
    effective KV bandwidth (bytes the dense read would stream) — decode
    attention is memory-bound, so that's the roofline axis. Each fused/ref
    pair is allclose-checked before timing (the benchmark doubles as an
    interpret-mode parity gate)."""
    b, hk, g, d, page = 8, 4, 4, 64, 16
    h = hk * g
    rows = []
    for t in (128, 512):
        q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
        lens = jnp.asarray(rng.integers(1, t + 1, size=b), jnp.int32)
        kv_bytes = 2 * b * t * hk * d * 4            # dense f32 K+V stream

        # identity page mapping is enough for timing — the kernel's
        # gather cost doesn't depend on the permutation
        npg = t // page
        pool_k = jnp.asarray(rng.normal(size=(b * npg, page, hk, d)),
                             jnp.float32)
        pool_v = jnp.asarray(rng.normal(size=(b * npg, page, hk, d)),
                             jnp.float32)
        table = jnp.arange(b * npg, dtype=jnp.int32).reshape(b, npg)
        pk, pv = kv_quantize(pool_k, d), kv_quantize(pool_v, d)
        pargs = dict(k_scale=pk.scale, k_zero=pk.zero, v_scale=pv.scale,
                     v_zero=pv.zero, group_size=d)

        cases = [
            ("paged_dense", lambda use: ops.decode_attn_paged(
                q, pool_k, pool_v, table, lens, use_pallas=use)),
            ("paged_int8", lambda use: ops.decode_attn_paged(
                q, pk.codes, pv.codes, table, lens, use_pallas=use,
                **pargs)),
        ]
        for name, fn in cases:
            np.testing.assert_allclose(np.asarray(fn(True)),
                                       np.asarray(fn(False)),
                                       atol=1e-4, rtol=1e-4)
            for use in (True, False):
                us = timed(lambda: fn(use))
                label = "fused" if use else "ref"
                rows.append(_row(f"decode_attn_{name}_t{t}[{label}]", us,
                                 kv_bytes / us / 1e3, "GB/s"))
    return rows


def main():
    rows = run()
    print("kernel,us_per_call,derived")
    for r in rows:
        print(f"{r['kernel']},{r['us_per_call']:.1f},"
              f"{r['derived_value']:.1f}{r['derived_unit']}")
    path = emit_json("kernels", {"rows": rows})
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
