"""Distributed AWP — the paper's technique as an SPMD-first feature.

Two schedules (DESIGN.md §2/§4):

* ``awp_prune_rowsharded`` — rows of Θ are independent sub-problems (Eq. 4),
  so d_out is sharded across the ENTIRE mesh and C is replicated: the PGD
  loop runs with **zero collectives**. The default whenever C fits per-device
  (d_in ≤ ~50k f32).

* ``awp_prune_colsharded`` — for huge-fan-in layers where replicated C would
  blow HBM (nemotron's d_ff=73728 → C is 21.7 GB f32): C is row-sharded over
  'model', each shard computes its partial (W−Θ)·C_shard, one psum per
  iteration rebuilds the full-width Z, and the row-top-k projection is local.
  Collective volume = |Z| per iteration — reported in §Roofline.

Both are pure jit-able functions; the launcher lowers them for the dry-run.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import projections as proj
from repro.sharding import ShardingRules


def awp_prune_rowsharded_fn(k: int, eta, iters: int):
    """The zero-collective PGD loop body (rows independent, C replicated)."""
    def run(w, c):
        theta = proj.topk_row(w, k)            # Wanda-init done upstream
        def body(theta, _):
            z = theta + eta * (w - theta) @ c
            return proj.topk_row(z, k), None
        theta, _ = jax.lax.scan(body, theta, None, length=iters)
        return theta
    return run


def rowsharded_shardings(rules: ShardingRules, d_out: Optional[int] = None):
    """(w, c) in-shardings + theta out-sharding for the row-parallel loop.

    Graded fallback when d_out doesn't divide the whole mesh (e.g.
    internvl2's 896 rows vs 256 chips): drop trailing mesh axes until the
    row count divides."""
    mesh = rules.mesh
    axes = tuple(rules.rows_axes)
    if d_out is not None:
        while axes and d_out % rules.axis_size(axes) != 0:
            axes = axes[:-1]
    row_sh = NamedSharding(mesh, P(axes if axes else None, None))
    rep = NamedSharding(mesh, P(None, None))
    return (row_sh, rep), row_sh


def awp_prune_rowsharded(w: jax.Array, c: jax.Array, k: int, eta, iters: int,
                         rules: ShardingRules):
    """Execute the row-sharded loop (dry-run lowers the fn directly)."""
    run = awp_prune_rowsharded_fn(k, eta, iters)
    if rules.mesh is None:
        return run(w, c)
    (in_w, in_c), out_sh = rowsharded_shardings(rules, w.shape[0])
    return jax.jit(run, in_shardings=(in_w, in_c), out_shardings=out_sh)(w, c)


def awp_prune_colsharded_fn(k: int, eta, iters: int, rules: ShardingRules):
    """Builds the shard_map'd column-sharded PGD loop (for lowering or
    execution). Layout: w/theta (rows over batch axes, d_in over model);
    c (d_in over model on axis 0, full axis 1)."""
    mesh = rules.mesh
    assert mesh is not None and rules.tp_axis is not None
    tp = rules.tp_axis
    n_tp = mesh.shape[tp]
    dp = rules.batch_axes

    def local(w_loc, c_loc):
        # w_loc: (r_loc, k_loc); c_loc: (k_loc, d_in)
        d_in = c_loc.shape[1]
        k_loc = c_loc.shape[0]
        my = jax.lax.axis_index(tp)

        def col_slice(z_full):
            return jax.lax.dynamic_slice_in_dim(z_full, my * k_loc, k_loc, 1)

        theta = None

        def body(theta_loc, _):
            resid = w_loc - theta_loc                       # (r_loc, k_loc)
            partial = resid.astype(jnp.float32) @ c_loc.astype(jnp.float32)
            z_resid = jax.lax.psum(partial, tp)             # (r_loc, d_in) full
            # need full-width theta for projection: gather local cols
            theta_full = jax.lax.all_gather(theta_loc, tp, axis=1, tiled=True)
            z = theta_full + eta * z_resid
            proj_full = proj.topk_row(z, k)
            return col_slice(proj_full), None

        theta0_full = proj.topk_row(
            jax.lax.all_gather(w_loc, tp, axis=1, tiled=True), k)
        theta_loc, _ = jax.lax.scan(body, col_slice(theta0_full), None,
                                    length=iters)
        return theta_loc

    def fn(w, c):
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(dp, tp), P(tp, None)),
            out_specs=P(dp, tp),
            check_vma=False)(w, c)

    return fn


def calib_c_distributed(acts: jax.Array, rules: ShardingRules) -> jax.Array:
    """Form C = (1/n)XᵀX with tokens sharded over the batch axes: local
    outer-product + one psum — the only collective of calibration."""
    mesh = rules.mesh
    if mesh is None:
        a = acts.reshape(-1, acts.shape[-1]).astype(jnp.float32)
        return a.T @ a / a.shape[0]
    dp = rules.batch_axes

    def local(a_loc):
        a = a_loc.reshape(-1, a_loc.shape[-1]).astype(jnp.float32)
        c_sum = jax.lax.psum(a.T @ a, dp)
        n = jax.lax.psum(jnp.float32(a.shape[0]), dp)
        return c_sum / n

    return jax.shard_map(local, mesh=mesh,
                         in_specs=P(dp, None, None) if acts.ndim == 3 else P(dp, None),
                         out_specs=P(None, None), check_vma=False)(acts)


__all__ = ["awp_prune_rowsharded", "awp_prune_rowsharded_fn",
           "rowsharded_shardings", "awp_prune_colsharded_fn",
           "calib_c_distributed"]
