"""Explicit data-parallel training step with wire-level int8 gradient psum.

This is the explicit-collective (shard_map) counterpart of train_step.py used
where we control the all-reduce directly: the model is replicated, the batch
shards over the given axes, per-device grads are quantized int8 with error
feedback and psum'd as integers — 4x less DP traffic, convergence preserved by
the residual (tests/test_compress.py). The production GSPMD path simulates the
same numerics via compress_roundtrip (see train/compress.py docstring).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.train import compress as C
from repro.train import optim as O
from repro.train.train_step import TrainState

P = jax.sharding.PartitionSpec


def build_dp_compressed_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    optimizer: O.Optimizer,
    mesh: jax.sharding.Mesh,
    dp_axes: tuple[str, ...],
):
    """loss_fn(params, local_batch) must be pure-local (dist=None inside)."""
    ax = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]

    def local_grads(params, err, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        out = jax.tree.map(lambda g, e: C.psum_int8(g, dp_axes, e),
                           grads, err)
        grads = jax.tree.map(lambda t: t[0] / n_dp, out,
                             is_leaf=lambda t: isinstance(t, tuple))
        new_err = jax.tree.map(lambda t: t[1], out,
                               is_leaf=lambda t: isinstance(t, tuple))
        loss = jax.lax.psum(loss, dp_axes) / n_dp
        return loss, grads, new_err

    def batch_specs(batch):
        return jax.tree.map(
            lambda x: P(ax, *([None] * (x.ndim - 1))), batch)

    def step(state: TrainState, batch):
        sharded = jax.shard_map(
            local_grads, mesh=mesh,
            in_specs=(P(), P(), batch_specs(batch)),
            out_specs=(P(), P(), P()),
        )
        loss, grads, err = sharded(state.params, state.err_state, batch)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                              state.params, updates)
        return (TrainState(params=params, opt_state=opt_state,
                           step=state.step + 1, err_state=err),
                {"loss": loss})

    return step
