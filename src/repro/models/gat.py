"""GAT (Veličković et al., arXiv:1710.10903) on segment ops.

Message passing = SDDMM (per-edge attention scores) -> segment-softmax over
in-edges -> SpMM (weighted scatter-sum), all built on jax.ops.segment_* since
JAX has no CSR (kernel_taxonomy §B.3). Distribution shards the EDGE LIST over
every mesh axis with full-size node partials psum'd — the paper's stage-2/3
dataflow; the §3.2 greedy balancer assigns edges by degree (DESIGN.md §4).

Three input forms, one kernel:
  full graph  — edge_src/edge_dst over the whole graph
  sampled     — padded bipartite blocks from sparse/sampler.py
  batched mol — block-diagonal edge index over padded small graphs
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.embedding import DistCtx
from repro.models.common import dense_init, shard
from repro.sparse.ops import segment_softmax

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class GATConfig:
    name: str
    d_feat: int
    n_classes: int
    n_layers: int = 2
    d_hidden: int = 8
    n_heads: int = 8
    dtype: Any = jnp.float32
    neg_slope: float = 0.2

    def param_count(self) -> int:
        n = self.d_feat * self.d_hidden * self.n_heads
        n += 2 * self.n_heads * self.d_hidden
        hid = self.d_hidden * self.n_heads
        for _ in range(self.n_layers - 2):
            n += hid * hid + 2 * hid
        n += hid * self.n_classes + 2 * self.n_classes
        return n


def init_params(cfg: GATConfig, key) -> dict:
    layers = []
    dims_in = [cfg.d_feat] + [cfg.d_hidden * cfg.n_heads] * (cfg.n_layers - 1)
    heads = [cfg.n_heads] * (cfg.n_layers - 1) + [1]
    outs = [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
    ks = jax.random.split(key, cfg.n_layers * 3)
    for i in range(cfg.n_layers):
        H, O = heads[i], outs[i]
        layers.append({
            "w": dense_init(ks[3 * i], (dims_in[i], H * O), dtype=cfg.dtype),
            "a_src": dense_init(ks[3 * i + 1], (H, O), dtype=cfg.dtype),
            "a_dst": dense_init(ks[3 * i + 2], (H, O), dtype=cfg.dtype),
        })
    return {"layers": layers}


def gat_layer(lw: dict, h_src: Array, h_dst: Array, edge_src: Array,
              edge_dst: Array, edge_mask: Array, n_dst: int, *, heads: int,
              out: int, neg_slope: float, dist: DistCtx | None,
              final: bool) -> Array:
    """One GAT conv. h_src: (Ns, F) features of message sources; h_dst:
    (Nd, F) of updated nodes; edges are (src local, dst local) with mask."""
    z_src = (h_src @ lw["w"]).reshape(-1, heads, out)
    z_dst = (h_dst @ lw["w"]).reshape(-1, heads, out)
    alpha_src = jnp.einsum("nho,ho->nh", z_src, lw["a_src"])
    alpha_dst = jnp.einsum("nho,ho->nh", z_dst, lw["a_dst"])

    def agg(e_src, e_dst, e_mask):
        # SDDMM: per-edge scores
        s = alpha_src[e_src] + alpha_dst[e_dst]                  # (E, H)
        s = jax.nn.leaky_relu(s, neg_slope)
        s = jnp.where(e_mask[:, None], s, -1e30)
        att = segment_softmax(s, e_dst, n_dst)                   # (E, H)
        att = jnp.where(e_mask[:, None], att, 0.0)
        msg = z_src[e_src] * att[..., None]                      # (E, H, O)
        return jax.ops.segment_sum(msg, e_dst, n_dst)            # (Nd, H, O)

    if dist is None:
        hz = agg(edge_src, edge_dst, edge_mask)
    else:
        # edge-sharded: each shard scatters into a full-size node buffer,
        # partials psum'd. NOTE: segment_softmax is computed per-shard which
        # requires the denominators to combine — so we split it: compute
        # unnormalized exp and normalizers as separate psums.
        P = jax.sharding.PartitionSpec
        axes = tuple(dist.dp_axes) + (dist.bank_axis,)
        ax = axes if len(axes) > 1 else axes[0]

        def fn(e_src, e_dst, e_mask):
            s = alpha_src[e_src] + alpha_dst[e_dst]
            s = jax.nn.leaky_relu(s, neg_slope)
            s = jnp.where(e_mask[:, None], s, -1e30)
            # global segment softmax across shards: max -> exp -> sum. The max
            # is a constant shift (softmax-invariant) => stop_gradient, which
            # also sidesteps pmax's missing differentiation rule.
            m_loc = jax.lax.stop_gradient(jax.ops.segment_max(s, e_dst, n_dst))
            m = jax.lax.pmax(jnp.where(jnp.isfinite(m_loc), m_loc, -1e30),
                             axes)
            ex = jnp.exp(s - m[e_dst])
            ex = jnp.where(e_mask[:, None], ex, 0.0)
            denom = jax.lax.psum(jax.ops.segment_sum(ex, e_dst, n_dst), axes)
            msg = z_src[e_src] * (ex / jnp.maximum(denom[e_dst], 1e-20))[..., None]
            return jax.lax.psum(jax.ops.segment_sum(msg, e_dst, n_dst), axes)

        hz = jax.shard_map(
            fn, mesh=dist.mesh,
            in_specs=(P(ax), P(ax), P(ax)), out_specs=P(),
        )(edge_src, edge_dst, edge_mask)

    if final:
        return hz.mean(axis=1)                                   # (Nd, n_classes)
    return jax.nn.elu(hz.reshape(hz.shape[0], heads * out))


def forward_full(cfg: GATConfig, params: dict, batch: dict,
                 dist: DistCtx | None = None) -> Array:
    """Full-graph forward: features (N, F), edge_src/dst (E,) -> logits (N, C)."""
    h = batch["features"].astype(cfg.dtype)
    e_src, e_dst = batch["edge_src"], batch["edge_dst"]
    e_mask = batch.get("edge_mask", jnp.ones_like(e_src, bool))
    n = h.shape[0]
    for i, lw in enumerate(params["layers"]):
        final = i == cfg.n_layers - 1
        heads = 1 if final else cfg.n_heads
        out = cfg.n_classes if final else cfg.d_hidden
        h = gat_layer(lw, h, h, e_src, e_dst, e_mask, n, heads=heads, out=out,
                      neg_slope=cfg.neg_slope, dist=dist, final=final)
    return h


def forward_blocks(cfg: GATConfig, params: dict, batch: dict,
                   dist: DistCtx | None = None) -> Array:
    """Sampled mini-batch forward over bipartite blocks (outermost first).

    Per-block dst counts are derived STATICALLY from array shapes (dst nodes
    of block i are the src prefix of block i+1): the innermost dst count is
    len(labels) (the seeds), and walking outward each src set is
    dst ++ sampled neighbors, so  ndst[i] = ndst[i+1] + len(edges[i+1]).
    """
    n_blocks = cfg.n_layers
    ndst = [0] * n_blocks
    ndst[-1] = batch["labels"].shape[0]
    for i in range(n_blocks - 2, -1, -1):
        ndst[i] = ndst[i + 1] + batch[f"block{i + 1}_src"].shape[0]
    h = batch["block0_feats"].astype(cfg.dtype)
    for i in range(n_blocks):
        lw = params["layers"][i]
        final = i == cfg.n_layers - 1
        heads = 1 if final else cfg.n_heads
        out = cfg.n_classes if final else cfg.d_hidden
        e_src = batch[f"block{i}_src"]
        e_dst = batch[f"block{i}_dst"]
        e_mask = batch[f"block{i}_mask"]
        n_dst = ndst[i]
        # dst nodes are the first n_dst entries of the src set by construction
        h_dst = h[:n_dst]
        h = gat_layer(lw, h, h_dst, e_src, e_dst, e_mask, n_dst, heads=heads,
                      out=out, neg_slope=cfg.neg_slope, dist=dist, final=final)
    return h


def masked_ce_loss(logits: Array, labels: Array, mask: Array) -> Array:
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logits.astype(jnp.float32),
                             labels[:, None].clip(0), axis=-1)[:, 0]
    per = jnp.where(mask, lse - ll, 0.0)
    return per.sum() / jnp.maximum(mask.sum(), 1)


def loss_full(cfg, params, batch, dist=None):
    logits = forward_full(cfg, params, batch, dist)
    return masked_ce_loss(logits, batch["labels"], batch["label_mask"])


def loss_blocks(cfg, params, batch, dist=None):
    logits = forward_blocks(cfg, params, batch, dist)
    return masked_ce_loss(logits, batch["labels"], batch["label_mask"])


def loss_molecule(cfg, params, batch, dist=None):
    """Batched small graphs (block-diag edges): mean-pool readout per graph."""
    logits = forward_full(cfg, params, batch, dist)              # (B*Nn, C)
    gid = batch["graph_ids"]
    n_graphs = batch["labels"].shape[0]
    pooled = jax.ops.segment_sum(logits, gid, n_graphs)
    cnt = jax.ops.segment_sum(jnp.ones_like(gid, logits.dtype), gid, n_graphs)
    pooled = pooled / jnp.maximum(cnt, 1.0)[:, None]
    return masked_ce_loss(pooled, batch["labels"],
                          jnp.ones(n_graphs, bool))
