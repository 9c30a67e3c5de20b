"""shard_map'd communication patterns shared across the model zoo.

``seqsharded_decode_attention`` is the flash-decode combine that makes
``long_500k`` (524k-token KV cache, batch 1) fit: the KV cache is sharded on
its sequence axis over ``seq_axes``; each shard computes a partial softmax
(running max / sum-exp / weighted values) over its slice and the partials are
combined with pmax/psum — numerically identical to full attention, O(S/n) HBM
per device, O(Hq*Dh) bytes on the wire.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.embedding import DistCtx

Array = jax.Array
P = jax.sharding.PartitionSpec

_NEG = -1e30


def all_mesh_axes(dist: DistCtx) -> tuple[str, ...]:
    """Every mesh axis, as one PartitionSpec entry — shards a big leading dim
    (candidate sets, negative samples) over the whole slice."""
    return tuple(dist.mesh.axis_names)


def _decode_attention_local(q: Array, k_new: Array, v_new: Array,
                            k_cache: Array, v_cache: Array, pos: Array,
                            ) -> tuple[Array, Array, Array]:
    """Reference semantics. q (B, Hq, Dh); k/v_new (B, Hkv, Dh);
    k/v_cache (B, S, Hkv, Dh); pos () int32 = slot for the new token.
    Returns (attn (B, Hq, Dh), k_cache', v_cache')."""
    B, Hq, Dh = q.shape
    Hkv = k_new.shape[1]
    G = Hq // Hkv
    S = k_cache.shape[1]
    kc = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k_new[:, None].astype(k_cache.dtype), pos, axis=1)
    vc = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v_new[:, None].astype(v_cache.dtype), pos, axis=1)
    qg = q.reshape(B, Hkv, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, kc.astype(jnp.float32),
                   preferred_element_type=jnp.float32) / np.sqrt(Dh)
    mask = jnp.arange(S) <= pos
    s = jnp.where(mask[None, None, None, :], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", p, vc.astype(jnp.float32))
    return o.reshape(B, Hq, Dh).astype(q.dtype), kc, vc


def seqsharded_decode_attention(q: Array, k_new: Array, v_new: Array,
                                k_cache: Array, v_cache: Array, pos: Array,
                                *, dist: DistCtx | None = None,
                                seq_axes: tuple[str, ...] = ("model",),
                                ) -> tuple[Array, Array, Array]:
    """One decode step of GQA attention with a sequence-sharded KV cache.

    The shard owning position ``pos`` writes the new K/V row; every shard
    computes a masked partial softmax over its cache slice; partials combine
    across ``seq_axes`` with the flash-decode (m, l, o) rescaling identity.
    """
    if dist is None:
        return _decode_attention_local(q, k_new, v_new, k_cache, v_cache, pos)

    mesh = dist.mesh
    n_seq = int(np.prod([mesh.shape[a] for a in seq_axes]))
    B, Hq, Dh = q.shape
    S = k_cache.shape[1]
    if n_seq == 1 or S % n_seq != 0:
        return _decode_attention_local(q, k_new, v_new, k_cache, v_cache, pos)

    Hkv = k_new.shape[1]
    G = Hq // Hkv
    dp_eff = tuple(a for a in dist.dp_axes if a not in seq_axes)
    n_dp = int(np.prod([mesh.shape[a] for a in dp_eff])) if dp_eff else 1
    bspec = None
    if dp_eff and B % n_dp == 0:
        bspec = dp_eff if len(dp_eff) > 1 else dp_eff[0]
    seq_entry = seq_axes if len(seq_axes) > 1 else seq_axes[0]
    s_loc = S // n_seq

    def fn(q, kn, vn, kc, vc, pos):
        # linear shard index along the (possibly multi-axis) seq sharding
        idx = jnp.zeros((), jnp.int32)
        for a in seq_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        off = idx * s_loc
        b = q.shape[0]

        # the owning shard inserts the new K/V row; others keep their slice
        lp = jnp.clip(pos - off, 0, s_loc - 1)
        owns = (pos >= off) & (pos < off + s_loc)
        kc_new = jax.lax.dynamic_update_slice_in_dim(
            kc, kn[:, None].astype(kc.dtype), lp, axis=1)
        vc_new = jax.lax.dynamic_update_slice_in_dim(
            vc, vn[:, None].astype(vc.dtype), lp, axis=1)
        kc = jnp.where(owns, kc_new, kc)
        vc = jnp.where(owns, vc_new, vc)

        qg = q.reshape(b, Hkv, G, Dh).astype(jnp.float32)
        s = jnp.einsum("bhgd,bshd->bhgs", qg, kc.astype(jnp.float32),
                       preferred_element_type=jnp.float32) / np.sqrt(Dh)
        mask = (off + jnp.arange(s_loc)) <= pos
        s = jnp.where(mask[None, None, None, :], s, _NEG)
        m = s.max(-1)                                   # (b, Hkv, G)
        m_g = jax.lax.pmax(m, seq_axes)
        p = jnp.exp(s - m_g[..., None])                 # 0 on masked shards
        l_g = jax.lax.psum(p.sum(-1), seq_axes)
        o = jnp.einsum("bhgs,bshd->bhgd", p, vc.astype(jnp.float32))
        o_g = jax.lax.psum(o, seq_axes)
        out = o_g / jnp.maximum(l_g, 1e-30)[..., None]
        return out.reshape(b, Hq, Dh).astype(q.dtype), kc, vc

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(bspec, None, None), P(bspec, None, None),
                  P(bspec, None, None),
                  P(bspec, seq_entry, None, None),
                  P(bspec, seq_entry, None, None), P()),
        out_specs=(P(bspec, None, None),
                   P(bspec, seq_entry, None, None),
                   P(bspec, seq_entry, None, None)),
    )(q, k_new, v_new, k_cache, v_cache, pos)
