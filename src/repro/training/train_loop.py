"""Training loop substrate: train_step factory with microbatched gradient
accumulation, mixed precision, donation, and an explicit-DP (shard_map)
variant with compressed gradient all-reduce.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.optim.optimizers import OptimizerConfig, make_optimizer
from repro.optim import grad_compress
from repro.sharding import ShardingRules, NO_RULES


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: OptimizerConfig = OptimizerConfig()
    microbatches: int = 1          # gradient-accumulation steps per update
    compute_dtype: Any = jnp.float32
    accum_dtype: Any = jnp.float32  # grad-accumulation carry dtype
    unroll_accum: bool = False      # python-loop accumulation (cost lowering
                                    # only: exposes per-microbatch collectives
                                    # to HloCostAnalysis; see launch/dryrun.py)


def init_train_state(model, key) -> Dict[str, Any]:
    params = model.init(key)
    opt_init, _ = make_optimizer(TrainConfig().optimizer)
    return {"params": params, "opt": opt_init(params),
            "step": jnp.zeros((), jnp.int32)}


def make_train_step(model, tcfg: TrainConfig):
    """Returns train_step(state, batch) -> (state, metrics).

    With microbatches > 1 the batch's leading dim is split and gradients are
    accumulated in a lax.scan (each microbatch is rematerialized in the
    backward pass — memory = one microbatch's activations)."""
    opt_init, opt_update = make_optimizer(tcfg.optimizer)

    def loss_fn(params, mb):
        loss, metrics = model.loss(params, mb)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def single(params, batch):
        (loss, metrics), grads = grad_fn(params, batch)
        return loss, grads, metrics

    def accumulated(params, batch):
        n = tcfg.microbatches
        def split(x):
            # (B, ...) -> (n_micro, B/n, ...) with the *second* axis carrying
            # the data-parallel sharding: element (i, j) = batch[j*n + i], so
            # each microbatch spans all DP shards (j maps to devices).
            b = x.shape[0]
            return x.reshape(b // n, n, *x.shape[1:]).swapaxes(0, 1)
        mbs = jax.tree.map(split, batch)
        # scan-based accumulation: the carry is double-buffered by XLA, so
        # the accumulator dtype is configurable — f32 by default, bf16 on
        # the 100B+ dry-run configs where 2×params-f32 of temp won't fit
        # (tradeoff note in EXPERIMENTS.md §Dry-run).
        acc_t = tcfg.accum_dtype
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_t), params)

        def body(carry, mb):
            loss_acc, gacc = carry
            loss, grads, _ = single(params, mb)
            gacc = jax.tree.map(
                lambda a, g: (a.astype(jnp.float32)
                              + g.astype(jnp.float32) / n).astype(acc_t),
                gacc, grads)
            return (loss_acc + loss / n, gacc), None

        if tcfg.unroll_accum:
            carry = (jnp.float32(0.0), zero)
            for i in range(n):
                carry, _ = body(carry, jax.tree.map(lambda x: x[i], mbs))
            loss, grads = carry
            return loss, grads, {}
        (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), zero), mbs)
        return loss, grads, {}

    def train_step(state, batch):
        if tcfg.microbatches > 1:
            loss, grads, _ = accumulated(state["params"], batch)
        else:
            loss, grads, _ = single(state["params"], batch)
        params, opt, om = opt_update(grads, state["opt"], state["params"])
        new_state = {"params": params, "opt": opt, "step": state["step"] + 1}
        metrics = {"loss": loss, **om}
        return new_state, metrics

    return train_step, opt_init


def make_train_step_ddp(model, tcfg: TrainConfig, rules: ShardingRules, *,
                        compress: Optional[str] = None, topk_frac: float = 0.01):
    """Explicit data-parallel train step under shard_map: params replicated
    across the DP axes, per-device gradients synced with a compressed
    all-reduce (compress = None | 'int8' | 'topk_ef').

    This variant exposes the gradient-sync collective so volume-reduction
    tricks are real (they appear in the lowered HLO and in the §Roofline
    collective term). It composes with the pjit TP sharding of everything
    else only at small TP degree; the flagship production path remains the
    pjit step — this is the distributed-optimization testbed.
    """
    assert rules.mesh is not None
    dp_axes = rules.batch_axes
    opt_init, opt_update = make_optimizer(tcfg.optimizer)
    from jax.sharding import PartitionSpec as P

    def local_grads(params, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, batch)[0])(params)
        return loss, grads

    def step_fn(state, batch):
        def shard_body(params, opt, step, local_batch):
            loss, grads = local_grads(params, local_batch)
            ax = dp_axes if len(dp_axes) > 1 else dp_axes[0]
            if compress == "int8":
                grads = jax.tree.map(
                    lambda g: grad_compress.int8_psum(g, ax), grads)
            else:
                grads = jax.tree.map(
                    lambda g: jax.lax.pmean(g, ax), grads)
            loss = jax.lax.pmean(loss, ax)
            params2, opt2, om = opt_update(grads, opt, params)
            return params2, opt2, step + 1, loss, om["grad_norm"]

        batch_spec = jax.tree.map(lambda _: P(dp_axes), batch)
        rep = jax.tree.map(lambda _: P(), state["params"])
        opt_spec = jax.tree.map(lambda _: P(), state["opt"])
        params2, opt2, step2, loss, gn = jax.shard_map(
            shard_body, mesh=rules.mesh,
            in_specs=(rep, opt_spec, P(), batch_spec),
            out_specs=(rep, opt_spec, P(), P(), P()),
            check_vma=False,
        )(state["params"], state["opt"], state["step"], batch)
        return ({"params": params2, "opt": opt2, "step": step2},
                {"loss": loss, "grad_norm": gn})

    return step_fn, opt_init


__all__ = ["TrainConfig", "make_train_step", "make_train_step_ddp",
           "init_train_state"]
