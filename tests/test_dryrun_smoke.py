"""Dry-run machinery smoke tests (subprocess with fake devices): production
mesh construction, one tiny-cell lower+compile, roofline parser."""
from conftest import run_multidevice
from repro.analysis.roofline import collective_bytes, Roofline


def test_collective_parser():
    hlo = """
  %all-gather.1 = f32[16,128]{1,0} all-gather(%x), replica_groups={}
  %ar = (f32[64]{0}, bf16[2,4]{1,0}) all-reduce(%a, %b), channel_id=1
  %dot.2 = f32[8,8]{1,0} dot(%p, %q)
  %a2a = f32[4,4]{1,0} all-to-all(%m), dimensions={0}
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 128 * 4
    assert out["all-reduce"] == 64 * 4 + 2 * 4 * 2
    assert out["all-to-all"] == 4 * 4 * 4
    assert out["reduce-scatter"] == 0


def test_roofline_terms():
    r = Roofline(flops_per_device=197e12, bytes_per_device=819e9,
                 collective_per_device=0.0, chips=256,
                 model_flops=197e12 * 256)
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 1.0) < 1e-9
    assert r.bottleneck in ("compute", "memory")
    assert abs(r.roofline_frac - 1.0) < 1e-6
    assert abs(r.useful_flops_frac - 1.0) < 1e-9


def test_production_mesh_and_tiny_cell_lowering():
    run_multidevice("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_production_mesh, make_mesh
from repro.configs import get_tiny_config
from repro.models import build_model, batch_specs
from repro.sharding import rules_for_cell, tree_shardings
from repro.training.train_loop import TrainConfig, make_train_step
from repro.optim import OptimizerConfig

# production mesh builds (needs 512 placeholder devices)
mesh_mp = make_production_mesh(multi_pod=True)
assert mesh_mp.devices.size == 512 and mesh_mp.axis_names == ("pod", "data", "model")
mesh_sp = make_production_mesh()
assert mesh_sp.devices.size == 256

# AOT lower+compile a tiny arch on a small mesh, ShapeDtypeStructs only
mesh = make_mesh((2, 2), ("data", "model"))
cfg = get_tiny_config("granite-8b")
rules = rules_for_cell(mesh, cfg.family, "train", global_batch=8)
model = build_model(cfg, rules, param_dtype=jnp.bfloat16, remat=True)
p_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
p_sh = tree_shardings(rules, model.param_logical_axes(), p_sds)
step_fn, opt_init = make_train_step(model, TrainConfig())
o_sds = jax.eval_shape(opt_init, p_sds)
from repro.sharding import opt_logical_axes
o_sh = tree_shardings(rules, opt_logical_axes("adamw", model.param_logical_axes(), p_sds), o_sds)
state_sds = {"params": p_sds, "opt": o_sds, "step": jax.ShapeDtypeStruct((), jnp.int32)}
state_sh = {"params": p_sh, "opt": o_sh, "step": NamedSharding(mesh, P())}
b_sds = batch_specs(cfg, 8, 16)
b_sh = {k: NamedSharding(mesh, P(("data",))) for k in b_sds}
with jax.set_mesh(mesh):
    compiled = jax.jit(step_fn, in_shardings=(state_sh, b_sh),
                       donate_argnums=0).lower(state_sds, b_sds).compile()
mem = compiled.memory_analysis()
assert mem.temp_size_in_bytes > 0
ca = compiled.cost_analysis()
if isinstance(ca, (list, tuple)): ca = ca[0]
assert ca.get("flops", 0) > 0
print("ok")
""", n_devices=512, timeout=900)
