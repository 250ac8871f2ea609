"""AOT compile guards: the main-path Pallas kernels (and ``topk_mask``) at
llama32-1b widths, and the paged engine's decode and chunk programs at the
tiny size, compiled by the TPU compiler for a described (not attached) v5e
chip.

Interpret mode accepts block shapes, casts and reshapes that Mosaic refuses;
these compiles catch that without a chip. The topology is described inside
a fixture (never at import, in ``skipif`` or in ``parametrize``) so every
test worker collects the same tests and only the worker that runs this file
loads the TPU compiler. The persistent compilation cache is off around the
compiles: entries for a described device cannot be read back here.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import pool_carry_faults
from repro.configs import get_config, get_tiny_config
from repro.kernels import (awp_pgd, decode_attn, dequant_matmul, kv_dequant,
                           ops, topk_mask)
from repro.models import build_model
from repro.serving import Engine, EngineConfig

CFG = get_config("llama32-1b")
D, F = CFG.d_model, CFG.d_ff                     # 2048, 8192
H, HK, HD = CFG.num_heads, CFG.num_kv_heads, CFG.resolved_head_dim
SLOTS, T, PAGE = 8, 1024, 16
PAGES = SLOTS * T // PAGE
GROUP = 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _pgd(batched: bool):
    if batched:       # gate/up bucket: B=2 items of (d_ff, d_model)
        shapes = [((2, F, D),), ((2, F, D),), ((2, D, D),), ((2,),)]
        return (lambda w, t, c, e: awp_pgd.awp_pgd_step(
            w, t, c, e, with_resid_norm=True)), shapes
    # down projection: the bucket of one that runs the 2-D kernel
    return (lambda w, t, c: awp_pgd.awp_pgd_step(
        w, t, c, 0.1, with_resid_norm=True)), [((D, F),), ((D, F),),
                                                ((F, F),)]


def _dequant(m: int, n: int, k: int):
    return (lambda x, p, s, z: dequant_matmul.dequant_matmul(
        x, p, s, z, group_size=GROUP)), [
        ((m, k),), ((n, k // 2), jnp.uint8), ((n, k // GROUP),),
        ((n, k // GROUP),)]


def _flash(int8: bool, stacked: bool = False, *, slots=SLOTS, t=T,
           pages=PAGES, layers=CFG.num_layers, heads=H, kv_heads=HK,
           head_dim=HD, dtype=jnp.float32):
    """flash_decode on one layer's page pool, or (stacked) the pools of
    every layer read at a traced layer index."""
    lead = (pages, PAGE)
    if stacked:
        lead = (layers,) + lead
    kv = [(lead + (kv_heads, head_dim), jnp.uint8 if int8 else dtype)]
    if int8:          # one group per head: f32 scale, uint8 zero-point
        kv += [(lead + (kv_heads, 1),), (lead + (kv_heads, 1), jnp.uint8)]
    shapes = [((slots, heads, head_dim),)] + kv + kv + [((slots,), jnp.int32)]
    shapes.append(((slots, t // PAGE), jnp.int32))
    if stacked:
        shapes.append(((), jnp.int32))

    def fn(q, *rest):
        layer = rest[-1] if stacked else None
        rest = rest[:-1] if stacked else rest
        table, lengths = rest[-1], rest[-2]
        planes = rest[:len(kv) * 2]
        if int8:
            kc, ks, kz, vc, vs, vz = planes
            return decode_attn.flash_decode(
                q, kc, vc, lengths, k_scale=ks, k_zero=kz, v_scale=vs,
                v_zero=vz, group_size=head_dim, table=table, layer=layer)
        return decode_attn.flash_decode(q, planes[0], planes[1], lengths,
                                        table=table, layer=layer)
    return fn, shapes


def _kv_dequant():
    rows = SLOTS * 256
    return (lambda c, s, z: kv_dequant.kv_dequant(c, s, z, group_size=HD)), [
        ((rows, HK * HD), jnp.uint8), ((rows, HK),), ((rows, HK), jnp.uint8)]


CASES = {
    "awp_pgd_2d": lambda: _pgd(False),
    "awp_pgd_batched": lambda: _pgd(True),
    **{f"dequant_matmul_m{m}_{n}x{k}": (lambda m=m, n=n, k=k:
                                         _dequant(m, n, k))
       for m in (8, 512) for n, k in ((D, D), (F, D), (D, F))},
    "flash_decode_paged_dense": lambda: _flash(False),
    "flash_decode_paged_int8": lambda: _flash(True),
    "flash_decode_paged_stacked_dense": lambda: _flash(False, True),
    "flash_decode_paged_stacked_int8": lambda: _flash(True, True),
    # bf16 pools keep the tiled grid, one page a grid step
    "flash_decode_paged_bf16": lambda: _flash(False, dtype=jnp.bfloat16),
    "flash_decode_paged_stacked_bf16": lambda: _flash(
        False, True, dtype=jnp.bfloat16),
    # head_dim 128: the paged loop over live pages
    "flash_decode_paged_loop_dense": lambda: _flash(False, head_dim=128),
    "flash_decode_paged_loop_stacked_dense": lambda: _flash(
        False, True, head_dim=128),
    # granite-8b.serve.assist: 12 rows, a 256-page table, the f32 pools of
    # 36 layers (a flat (36·1024, 16, 8, 128) pool in the kernel)
    "flash_decode_paged_loop_granite_cell": lambda: _flash(
        False, True, slots=12, t=4096, pages=1024, layers=36, heads=32,
        kv_heads=8, head_dim=128),
    "kv_dequant": _kv_dequant,
    "topk_mask": lambda: ((lambda z: topk_mask.topk_row(z, F // 2)),
                          [((D, F),)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s[0], s[1] if len(s) > 1 else jnp.float32,
                                 sharding=one_chip) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    if case.startswith("flash_decode_paged_loop"):
        # one grid step a row, whatever the table's length
        grids = [e.params["grid_mapping"].grid
                 for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
        assert grids == [(shapes[0][0][0],)]


@pytest.fixture
def native_kernels(monkeypatch):
    """The kernel wrappers lower natively (not in interpret mode), as they
    do on the chip; their traces from other tests are dropped around it."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    ops.decode_attn_paged.clear_cache()
    yield
    ops.decode_attn_paged.clear_cache()


@pytest.mark.parametrize("name", ["decode", "chunk", "decode_hd128"])
def test_paged_step_programs_keep_pools_in_place_on_v5e(
        name, one_chip, no_persistent_cache, native_kernels):
    """With the fused paged flash-decode kernel, the chip's decode and
    chunk programs take no slice of a pool, write none back and copy none:
    the stacked pools stay in the donated buffers. At head_dim 128 the
    decode program runs the paged loop over live pages."""
    cfg = get_tiny_config("llama32-1b")
    if name.endswith("_hd128"):
        cfg = dataclasses.replace(cfg, head_dim=128)
        name = name[:-len("_hd128")]
    model = build_model(cfg, remat=False)
    eng = Engine(model, model.init(jax.random.PRNGKey(0)), EngineConfig(
        num_slots=4, max_len=64, prompt_buckets=(16, 32), page_size=16,
        num_pages=19))
    fn, args = {"decode": (eng._decode, eng._decode_args),
                "chunk": (eng._chunk, eng._dummy_chunk_args)}[name]
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        jnp.shape(x), jnp.result_type(x), sharding=one_chip), args())
    hlo = fn.lower(*args).compile().as_text()
    assert pool_carry_faults(eng, hlo) == []
    if name == "decode":
        assert "tpu_custom_call" in hlo
