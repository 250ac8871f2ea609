"""Distribution tests (subprocess, 8 fake devices): sharding rules, MoE a2a
vs dense equivalence, row/col-sharded AWP equivalence, DDP+int8 training,
elastic checkpoint restore across mesh shapes."""
import numpy as np
import pytest

from conftest import run_multidevice
from repro.sharding import ShardingRules, rules_for_cell
from jax.sharding import PartitionSpec as P


def test_rules_adaptive_fallback_no_mesh():
    r = ShardingRules(mesh=None)
    assert r.spec(("batch", None, "tp"), (8, 4, 16)) == P(None, None, None)


def test_rules_for_cell_families():
    # no mesh: everything degrades to no-op rules
    r = rules_for_cell(None, "ssm", "train")
    assert r.mesh is None


def test_spec_divisibility_fallback():
    run_multidevice("""
import jax
from repro.launch.mesh import make_mesh
from repro.sharding import ShardingRules
from jax.sharding import PartitionSpec as P
mesh = make_mesh((2, 4), ("data", "model"))
r = ShardingRules.for_mesh(mesh)
# 36 doesn't divide model=4? 36/4=9 ok; use 37 -> fallback to None
assert r.spec((None, "tp"), (8, 37)) == P(None, None)
assert r.spec((None, "tp"), (8, 36)) == P(None, "model")
# duplicate axis use: second occurrence replicates
assert r.spec(("batch", "fsdp"), (8, 8)) == P(("data",), None)
assert r.spec(("rows", None), (16, 5)) == P(("data", "model"), None)
print("ok")
""")


def test_moe_a2a_equals_dense():
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get_tiny_config
from repro.models.moe import moe_params, moe_apply_dense, moe_apply_a2a
from repro.sharding import ShardingRules
from repro.launch.mesh import make_mesh
cfg = dataclasses.replace(get_tiny_config("qwen3-moe-235b-a22b"), capacity_factor=8.0)
mesh = make_mesh((2, 4), ("data", "model"))
rules = ShardingRules.for_mesh(mesh)
p = moe_params(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 64, cfg.d_model))
y_dense = moe_apply_dense(p, x, cfg)
with jax.set_mesh(mesh):
    y_a2a = jax.jit(lambda p, x: moe_apply_a2a(p, x, cfg, rules))(p, x)
np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_a2a), rtol=2e-4, atol=2e-4)
print("ok")
""")


def test_awp_row_and_col_sharded_equal_single_device():
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import distributed as dist, projections as proj
from repro.sharding import ShardingRules
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
rules = ShardingRules.for_mesh(mesh)
rng = np.random.default_rng(0)
w = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
x = jnp.asarray(rng.normal(size=(256, 64)), jnp.float32)
c = x.T @ x / 256
k, eta, iters = 32, float(2.0 / jnp.linalg.norm(c)), 10
ref = dist.awp_prune_rowsharded_fn(k, eta, iters)(w, c)
row = dist.awp_prune_rowsharded(w, c, k, eta, iters, rules)
np.testing.assert_allclose(np.asarray(row), np.asarray(ref), rtol=2e-4, atol=2e-4)
with jax.set_mesh(mesh):
    col = jax.jit(dist.awp_prune_colsharded_fn(k, eta, iters, rules))(w, c)
np.testing.assert_allclose(np.asarray(col), np.asarray(ref), rtol=2e-4, atol=2e-4)
print("ok")
""")


def test_calib_c_distributed():
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import distributed as dist
from repro.sharding import ShardingRules
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
rules = ShardingRules.for_mesh(mesh)
rng = np.random.default_rng(0)
a = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
ref = np.asarray(a.T @ a / 64)
with jax.set_mesh(mesh):
    c = jax.jit(lambda a: dist.calib_c_distributed(a, rules))(a)
np.testing.assert_allclose(np.asarray(c), ref, rtol=1e-4, atol=1e-5)
print("ok")
""")


def test_ddp_int8_training_converges():
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_tiny_config
from repro.models import build_model, make_batch
from repro.training.train_loop import TrainConfig, make_train_step_ddp
from repro.optim import OptimizerConfig
from repro.sharding import ShardingRules
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
rules = ShardingRules.for_mesh(mesh)
cfg = get_tiny_config("granite-8b")
model = build_model(cfg, remat=False)
tcfg = TrainConfig(optimizer=OptimizerConfig(lr=2e-3, warmup_steps=5, total_steps=100))
step_fn, opt_init = make_train_step_ddp(model, tcfg, rules, compress="int8")
params = model.init(jax.random.PRNGKey(0))
state = {"params": params, "opt": opt_init(params), "step": jnp.zeros((), jnp.int32)}
from repro.data import DataConfig, ZipfMarkov
gen = ZipfMarkov(DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=16))
with jax.set_mesh(mesh):
    jstep = jax.jit(step_fn)
    losses = []
    for i in range(25):
        t, l = gen.batch(i)
        state, m = jstep(state, {"tokens": jnp.asarray(t), "labels": jnp.asarray(l)})
        losses.append(float(m["loss"]))
assert losses[-1] < losses[0] - 0.5, losses
print("ok", losses[0], losses[-1])
""", timeout=900)


def test_elastic_checkpoint_restore_across_meshes():
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np, tempfile, os
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import save_checkpoint, restore_checkpoint
from repro.launch.mesh import make_mesh
rng = np.random.default_rng(0)
tree = {"w": jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)}
mesh1 = make_mesh((2, 4), ("data", "model"))
sh1 = {"w": NamedSharding(mesh1, P("data", "model"))}
t1 = jax.tree.map(lambda x, s: jax.device_put(x, s), tree, sh1)
with tempfile.TemporaryDirectory() as d:
    path = save_checkpoint(d, 1, t1)
    # restore onto a DIFFERENT mesh shape (elastic shrink/grow)
    mesh2 = make_mesh((8,), ("data",))
    sh2 = {"w": NamedSharding(mesh2, P("data", None))}
    t2 = restore_checkpoint(path, tree, sh2)
    np.testing.assert_array_equal(np.asarray(t2["w"]), np.asarray(tree["w"]))
    assert t2["w"].sharding.spec == P("data", None)
print("ok")
""")


def test_sharded_train_step_matches_single_device():
    """pjit-sharded tiny train step == unsharded step (numerics)."""
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_tiny_config
from repro.models import build_model, make_batch
from repro.training.train_loop import TrainConfig, make_train_step
from repro.optim import OptimizerConfig
from repro.sharding import ShardingRules, tree_shardings
from repro.launch.mesh import make_mesh
cfg = get_tiny_config("granite-8b")
mesh = make_mesh((2, 2), ("data", "model"))
rules = ShardingRules.for_mesh(mesh)
m_sharded = build_model(cfg, rules, remat=False)
m_plain = build_model(cfg, remat=False)
key = jax.random.PRNGKey(0)
params = m_plain.init(key)
batch = make_batch(cfg, key, 4, 16)
tcfg = TrainConfig(optimizer=OptimizerConfig(lr=1e-3))
step_s, opt_init = make_train_step(m_sharded, tcfg)
step_p, _ = make_train_step(m_plain, tcfg)
state = {"params": params, "opt": opt_init(params), "step": jnp.zeros((), jnp.int32)}
s_plain, mp = jax.jit(step_p)(state, batch)
with jax.set_mesh(mesh):
    p_sh = tree_shardings(rules, m_sharded.param_logical_axes(),
                          jax.eval_shape(m_sharded.init, key))
    sp = jax.device_put(params, p_sh)
    state_s = {"params": sp, "opt": opt_init(sp), "step": jnp.zeros((), jnp.int32)}
    b_sh = {k: NamedSharding(mesh, P(rules.batch_axes)) for k in batch}
    bs = jax.device_put(batch, b_sh)
    s_shard, ms = jax.jit(step_s)(state_s, bs)
assert abs(float(mp["loss"]) - float(ms["loss"])) < 2e-4
w1 = np.asarray(s_plain["params"]["blocks"]["attn"]["wq"])
w2 = np.asarray(s_shard["params"]["blocks"]["attn"]["wq"])
np.testing.assert_allclose(w1, w2, rtol=2e-3, atol=2e-4)
print("ok")
""", timeout=900)


def test_moe_a2a_packed_experts():
    """Stacked QTensor experts ride the all-to-all path when the expert
    count tiles the TP axis (slot factor r == 1): a2a == masked-dense on
    the same packed weights, and moe_apply auto-routes to a2a."""
    run_multidevice("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_tiny_config
from repro.models.moe import moe_params, moe_apply_dense, moe_apply_a2a, moe_apply
from repro.sharding import ShardingRules
from repro.launch.mesh import make_mesh
from repro.quant import QTensor
cfg = dataclasses.replace(get_tiny_config("qwen3-moe-235b-a22b"), capacity_factor=8.0)
mesh = make_mesh((2, 4), ("data", "model"))
rules = ShardingRules.for_mesh(mesh)
p = moe_params(jax.random.PRNGKey(0), cfg)
def pack(w):   # (E, d_in, d_out) dense stack -> stacked per-expert QTensor
    qts = [QTensor.from_dense(w[e].T, bits=4, group_size=16) for e in range(w.shape[0])]
    return jax.tree.map(lambda *a: jnp.stack(a), *qts)
pq = dict(p, wu=pack(p["wu"]), wd=pack(p["wd"]), wg=pack(p["wg"]))
x = jax.random.normal(jax.random.PRNGKey(1), (8, 64, cfg.d_model))
y_dense = moe_apply_dense(pq, x, cfg)
with jax.set_mesh(mesh):
    y_a2a = jax.jit(lambda p, x: moe_apply_a2a(p, x, cfg, rules))(pq, x)
    y_auto = jax.jit(lambda p, x: moe_apply(p, x, cfg, rules))(pq, x)
np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_a2a), rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(np.asarray(y_a2a), np.asarray(y_auto), rtol=0, atol=0)
print("ok")
""", timeout=900)


def test_qtensor_logical_axes_shard_packed_leaves():
    """adapt_logical_axes expands dense leaf axes into per-child QTensor
    axes; tree_shardings then shards packed/scale/zero over TP/FSDP instead
    of replicating (the packed-checkpoint-restore path)."""
    run_multidevice("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.launch.mesh import make_mesh
from repro.sharding import ShardingRules, adapt_logical_axes, tree_specs, tree_shardings, P
from repro.quant import QTensor
mesh = make_mesh((2, 4), ("data", "model"))
rules = ShardingRules.for_mesh(mesh)
w = jax.random.normal(jax.random.PRNGKey(0), (16, 64))   # paper (d_out, d_in)
qt = QTensor.from_dense(w, bits=4, group_size=32,
                        col_scale=jnp.ones((64,), jnp.float32))
stacked = jax.tree.map(lambda a: jnp.stack([a] * 3), qt)
params = {"blocks": {"attn": {"wq": stacked, "norm": jnp.ones((3, 8))}}}
axes = {"blocks": {"attn": {"wq": (None, "fsdp", "tp"), "norm": (None, None)}}}
adapted = adapt_logical_axes(axes, params)
wq_ax = adapted["blocks"]["attn"]["wq"]
assert isinstance(wq_ax, QTensor) and wq_ax.packed == (None, "tp", "fsdp")
specs = tree_specs(rules, adapted, jax.eval_shape(lambda: params))
wq = specs["blocks"]["attn"]["wq"]
assert wq.packed == P(None, "model", ("data",))          # sharded, not replicated
assert wq.scale == P(None, "model", ("data",))
assert wq.col_scale == P(None, ("data",))
assert specs["blocks"]["attn"]["norm"] == P(None, None)
sh = tree_shardings(rules, adapted, jax.eval_shape(lambda: params))
placed = jax.device_put(params, sh)                      # actually places on mesh
assert isinstance(placed["blocks"]["attn"]["wq"], QTensor)
assert isinstance(placed["blocks"]["attn"]["wq"].packed.sharding, NamedSharding)
assert str(placed["blocks"]["attn"]["wq"].packed.sharding.spec) == str(wq.packed)
print("ok")
""", timeout=900)
