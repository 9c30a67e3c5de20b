"""Pallas TPU kernel: DLRM pairwise-dot feature interaction.

z (B, F, D) -> upper-triangle of z·zᵀ, (B, F(F-1)/2). The MXU-friendly move:
compute the full (F, F) Gram matrix per batch tile with one (F, D)x(D, F)
matmul (D padded to 128 lanes by ops.py), then extract the triangle with an
iota mask + reshape — no per-pair scalar loops. The Gram tile lives entirely
in VMEM: F is small (27-40 for DLRM/xDeepFM) so tile_b x F x F fits easily.

Output is padded to P_pad (multiple of 128) columns; ops.py slices the valid
P = F(F-1)/2 prefix. Padding (not gathering) keeps the kernel store shape
lane-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dot_kernel(z_ref, out_ref, *, n_fields: int, n_pairs_pad: int):
    z = z_ref[...].astype(jnp.float32)          # (tile_b, F, D)
    gram = jax.lax.dot_general(
        z, z, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)     # (tile_b, F, F)
    iu = jax.lax.broadcasted_iota(jnp.int32, (n_fields, n_fields), 0)
    ju = jax.lax.broadcasted_iota(jnp.int32, (n_fields, n_fields), 1)
    upper = (ju > iu).reshape(-1)               # (F*F,) static mask
    flat = gram.reshape(gram.shape[0], -1)      # (tile_b, F*F)
    # stable-order compaction of the upper triangle into the padded output:
    # position of pair (i,j) = cumsum(upper)-1; scatter via one matmul with a
    # {0,1} selection matrix (static), MXU-friendly and layout-clean.
    pos = jnp.cumsum(upper.astype(jnp.int32)) - 1
    sel = jnp.where(
        upper[:, None]
        & (jax.lax.broadcasted_iota(jnp.int32, (n_fields * n_fields,
                                                n_pairs_pad), 1)
           == pos[:, None]),
        1.0, 0.0)                               # (F*F, P_pad) static
    out_ref[...] = (flat @ sel).astype(out_ref.dtype)


def dot_interaction_pallas(z: jax.Array, *, tile_b: int = 128,
                           interpret: bool = False) -> jax.Array:
    """z (B, F, D) -> (B, P_pad) where the first F(F-1)/2 cols are the pairs."""
    B, F, D = z.shape
    n_pairs = F * (F - 1) // 2
    n_pairs_pad = -(-n_pairs // 128) * 128
    tile_b = min(tile_b, B)
    assert B % tile_b == 0
    kernel = functools.partial(_dot_kernel, n_fields=F,
                               n_pairs_pad=n_pairs_pad)
    return pl.pallas_call(
        kernel,
        grid=(B // tile_b,),
        in_specs=[pl.BlockSpec((tile_b, F, D), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((tile_b, n_pairs_pad), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_pairs_pad), z.dtype),
        interpret=interpret, name="updlrm_dot_interaction",
    )(z)
