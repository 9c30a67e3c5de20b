"""Jit'd public wrappers around the Pallas kernels.

Handle TPU layout rules (pad D to 128 lanes — the §3.1 alignment rule, TPU
constants), pick interpret mode off-TPU automatically, and expose drop-in
replacements for the pure-jnp paths:

    embedding_bag(table, idx)            ~ ref.embedding_bag_ref
    cache_bag(emt, cache, c_idx, r_idx)  ~ ref.cache_bag_ref
    dot_interaction(z)                   ~ ref.dot_interaction_ref
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.cache_bag import cache_bag_pallas
from repro.kernels.dot_interaction import dot_interaction_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# one home for the alignment rules: kernels/embedding_bag.py
from repro.kernels.embedding_bag import pad_last_dim as _pad_dim
from repro.kernels.embedding_bag import pad_leading


def _pad_batch(idx: jax.Array, tile_b: int) -> tuple[jax.Array, int]:
    return pad_leading(idx, tile_b)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def embedding_bag(table: jax.Array, idx: jax.Array, *, tile_b: int = 8,
                  interpret: bool | None = None) -> jax.Array:
    """(V, D) x (B, L) -> (B, D). Pads B to the tile (the kernel wrapper
    lays D out on the 128 lanes)."""
    if interpret is None:
        interpret = not _on_tpu()
    ipad, b0 = _pad_batch(idx, tile_b)
    out = embedding_bag_pallas(table, ipad, tile_b=tile_b,
                               interpret=bool(interpret))
    return out[:b0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def embedding_bag_trainable(table: jax.Array, idx: jax.Array,
                            tile_b: int = 8) -> jax.Array:
    """Differentiable wrapper: Pallas kernel forward, scatter-add backward
    (the backward of a bag-sum IS a row scatter — XLA's native scatter is
    already the right kernel for it)."""
    return embedding_bag(table, idx, tile_b=tile_b)


def _bag_fwd(table, idx, tile_b):
    return embedding_bag(table, idx, tile_b=tile_b), (table.shape, idx)


def _bag_bwd(tile_b, res, ct):
    (shape, idx) = res
    B, L = idx.shape
    valid = idx >= 0
    safe = jnp.where(valid, idx, 0).reshape(-1)
    updates = jnp.broadcast_to(ct[:, None, :], (B, L, ct.shape[-1]))
    updates = jnp.where(valid[..., None], updates, 0).reshape(B * L, -1)
    d_table = jnp.zeros(shape, ct.dtype).at[safe].add(updates)
    return (d_table, None)


embedding_bag_trainable.defvjp(_bag_fwd, _bag_bwd)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def cache_bag(emt: jax.Array, cache: jax.Array, cache_idx: jax.Array,
              residual_idx: jax.Array, *, tile_b: int = 8,
              interpret: bool | None = None) -> jax.Array:
    """Fused Fig.-7 lookup: one kernel pass over both index streams."""
    if interpret is None:
        interpret = not _on_tpu()
    epad, d0 = _pad_dim(emt)
    cpad, _ = _pad_dim(cache)
    ci, b0 = _pad_batch(cache_idx, tile_b)
    ri, _ = _pad_batch(residual_idx, tile_b)
    out = cache_bag_pallas(epad, cpad, ci, ri, tile_b=tile_b,
                           interpret=bool(interpret))
    return out[:b0, :d0]


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def dot_interaction(z: jax.Array, *, tile_b: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """(B, F, D) -> (B, F(F-1)/2)."""
    if interpret is None:
        interpret = not _on_tpu()
    B, F, D = z.shape
    zpad, _ = _pad_dim(z)
    zb, b0 = _pad_batch(zpad, min(tile_b, max(8, B)))
    n_pairs = F * (F - 1) // 2
    out = dot_interaction_pallas(zb, tile_b=min(tile_b, zb.shape[0]),
                                 interpret=bool(interpret))
    return out[:b0, :n_pairs]
