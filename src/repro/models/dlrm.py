"""DLRM (Naumov et al., arXiv:1906.00091) with UpDLRM banked embeddings.

All sparse fields share ONE banked super-table (per-field row offsets), so the
paper's partitioners operate on the union vocabulary exactly like the DPU
deployment (each DPU group holds tiles of all tables; Fig. 4). Two lookup
flavours:

  * one-hot fields (Criteo-style ``dlrm-rm2``): dense gather (B, F) -> (B, F, D)
  * multi-hot bags (the paper's Table-1 datasets): (B, T, L) -> bag sums
    (B, T, D), optionally via the cache-aware rewritten form (cache ids +
    residual ids) — Fig. 7's dataflow.

The pairwise dot-product feature interaction is the Pallas ``dot_interaction``
kernel's reference path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.embedding import (
    BankedTable, DistCtx, banked_cache_residual_bag, banked_embedding_bag,
    banked_gather, tiered_embedding_bag)
from repro.models.common import dense_init, embed_init, shard, dp

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    name: str
    vocab_sizes: tuple[int, ...]       # per sparse field
    embed_dim: int
    n_dense: int
    bot_mlp: tuple[int, ...]           # hidden dims incl. final (== embed_dim)
    top_mlp: tuple[int, ...]           # hidden dims, final 1 appended
    multi_hot: int = 1                 # bag length per field (1 => one-hot)
    interaction: str = "dot"
    dtype: Any = jnp.float32
    # §Perf C2: table STORAGE dtype — bf16 halves every table-sized buffer
    # (gathers, grad scatter, optimizer r/w, stage-3 psum) while the row-wise
    # Adagrad accumulator stays fp32. Dense compute stays cfg.dtype.
    emb_dtype: Any = jnp.float32

    @property
    def n_sparse(self) -> int:
        return len(self.vocab_sizes)

    @property
    def total_vocab(self) -> int:
        return int(sum(self.vocab_sizes))

    def field_offsets(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.vocab_sizes)[:-1]]).astype(np.int64)

    def param_count(self) -> int:
        n = self.total_vocab * self.embed_dim
        dims = [self.n_dense, *self.bot_mlp]
        n += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
        n_inter = self.n_sparse + 1
        top_in = n_inter * (n_inter - 1) // 2 + self.embed_dim
        dims = [top_in, *self.top_mlp, 1]
        n += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
        return n


def _mlp_params(key, dims: Sequence[int], dtype) -> dict:
    ks = jax.random.split(key, len(dims) - 1)
    return {
        "w": [dense_init(k, (a, b), dtype=dtype)
              for k, a, b in zip(ks, dims[:-1], dims[1:])],
        "b": [jnp.zeros((b,), dtype) for b in dims[1:]],
    }


def mlp_apply(p: dict, x: Array, act=jax.nn.relu, final_act=None) -> Array:
    n = len(p["w"])
    for i, (w, b) in enumerate(zip(p["w"], p["b"])):
        x = x @ w + b
        if i < n - 1:
            x = act(x)
        elif final_act is not None:
            x = final_act(x)
    return x


def init_params(cfg: DLRMConfig, key, plan=None,
                rows_per_bank: int | None = None) -> tuple[dict, dict]:
    """Returns (params, statics). ``plan`` is a PartitionPlan over the union
    vocab; statics carries the row remap (untrained int arrays).

    ``rows_per_bank`` over-allocates each bank to a fixed capacity (>= the
    plan's max) so later plans can be swapped in-place without changing the
    packed shape — the adaptive-replanning contract (repro.workload)."""
    from repro.core.partitioning import uniform_partition
    k1, k2, k3 = jax.random.split(key, 3)
    if plan is None:
        plan = uniform_partition(cfg.total_vocab, 1)
    rows_per_bank = int(plan.max_rows_per_bank if rows_per_bank is None
                        else rows_per_bank)
    assert rows_per_bank >= plan.max_rows_per_bank
    packed = embed_init(k1, (plan.n_banks * rows_per_bank, cfg.embed_dim),
                        dtype=cfg.emb_dtype)
    params = {
        "emb_packed": packed,
        "bot": _mlp_params(k2, [cfg.n_dense, *cfg.bot_mlp], cfg.dtype),
        "top": _mlp_params(
            k3,
            [cfg.n_sparse * (cfg.n_sparse + 1) // 2 + cfg.embed_dim,
             *cfg.top_mlp, 1],
            cfg.dtype),
    }
    statics = {
        "remap_bank": jnp.asarray(plan.bank_of_row, jnp.int32),
        "remap_slot": jnp.asarray(plan.slot_of_row, jnp.int32),
        "n_banks": plan.n_banks,
        "rows_per_bank": rows_per_bank,
        "field_offsets": jnp.asarray(cfg.field_offsets(), jnp.int32),
    }
    return params, statics


def _banked(params: dict, statics: dict) -> BankedTable:
    return BankedTable(
        packed=params["emb_packed"],
        remap_bank=statics["remap_bank"],
        remap_slot=statics["remap_slot"],
        n_banks=statics["n_banks"],
        rows_per_bank=statics["rows_per_bank"],
    )


def dot_interaction(z: Array) -> Array:
    """z: (B, F, D) -> (B, F*(F-1)/2) upper-triangular pairwise dots.

    Reference path for kernels/dot_interaction.py.
    """
    B, F, D = z.shape
    zz = jnp.einsum("bfd,bgd->bfg", z, z, preferred_element_type=jnp.float32)
    iu, ju = np.triu_indices(F, k=1)
    return zz[:, iu, ju].astype(z.dtype)


def forward(cfg: DLRMConfig, params: dict, statics: dict, batch: dict,
            dist: DistCtx | None = None, *, backend: str = "auto",
            bwd_backend: str = "auto", tiered=None,
            replicated=None, bank_live: Array | None = None) -> Array:
    """batch: dense (B, n_dense) fp; sparse (B, F) int32 (one-hot fields) or
    (B, F, L) multi-hot. Returns logits (B,).

    ``backend`` selects the stage-2 lookup implementation (core/embedding.py):
    'jnp' scan, 'pallas' fused kernel, or 'auto'. ``bwd_backend`` selects the
    pallas forward's gradient scatter ('auto' follows ``backend``: a pallas
    training step keeps the backward's row traffic on the sorted-run scatter
    kernel). The multi-hot path hands the RAW (B, F, L) per-field ids plus
    ``field_offsets`` to ONE fused banked_embedding_bag call — all F fields
    in a single stage-2 pass, and no (B, F, L, D) gathered intermediate on
    either backend.

    ``tiered`` (a repro.quant.TieredTable quantized FROM ``emb_packed``'s
    layout) reroutes the lookup through the tiered-precision path: values
    come from the quantized payload (dequant in-kernel), gradients flow
    straight through onto ``params['emb_packed']``. The adaptive serve loop
    passes it as a jit ARGUMENT so a live re-tier swap feeds new same-shape
    arrays to the compiled step — zero recompiles (launch/serve.py --quant).
    One-hot fields fold into length-1 bags on this path (same semantics as
    the dense gather).

    ``replicated`` (a core.embedding.ReplicatedTable — the runtime's hot-row
    replica side table) reroutes the lookup through the replica-aware path:
    each bag picks one copy of each row via an in-kernel hash, so hot-row
    traffic splits across the copies' banks. Like ``tiered`` it rides the jit
    as an ARGUMENT with pinned shapes — a live replica-count swap is a pure
    argument change (launch/serve.py --replicate-k-max). Composes with
    ``bank_live``: a surviving copy covers a dead bank's reads before any
    read degrades to the zero row. One-hot fields fold into length-1 bags.
    Mutually exclusive with ``tiered`` (the replicas ARE the full-precision
    head; an in-kernel dequant+replica-select kernel is a ROADMAP item).

    ``bank_live`` ((n_banks,) bool jit argument) enables bounded-degraded
    serving through a bank failure: reads homed on dead banks resolve to the
    zero row (core/embedding.py). Not supported with ``tiered`` — the fault
    lane runs the full-precision path.
    """
    dense, sparse = batch["dense"], batch["sparse"]
    B = dense.shape[0]
    t = _banked(params, statics)
    if replicated is not None:
        if tiered is not None:
            raise ValueError("tiered x replicated serving is not wired — "
                             "replicas are the full-precision head "
                             "(ROADMAP.md)")
        from repro.core.embedding import replicated_embedding_bag
        bags = sparse if sparse.ndim == 3 else sparse[..., None]
        emb = replicated_embedding_bag(                          # (B, F, D)
            replicated, bags, dist, backend=backend,
            bwd_backend=bwd_backend,
            field_offsets=statics["field_offsets"], bank_live=bank_live)
    elif tiered is not None:
        if bank_live is not None:
            raise ValueError("bank_live degraded serving is not wired into "
                             "the tiered lookup path")
        bags = sparse if sparse.ndim == 3 else sparse[..., None]
        emb = tiered_embedding_bag(                              # (B, F, D)
            params["emb_packed"], tiered, bags, dist, backend=backend,
            bwd_backend=bwd_backend,
            field_offsets=statics["field_offsets"])
    elif sparse.ndim == 2:
        # one-hot fields: dense gather; per-field ids -> union-vocab rows
        rows = sparse + statics["field_offsets"][None, :]
        rows = jnp.where(sparse >= 0, rows, -1)
        emb = banked_gather(t, rows, dist, bank_live=bank_live)  # (B, F, D)
    else:
        emb = banked_embedding_bag(                              # (B, F, D)
            t, sparse, dist, backend=backend, bwd_backend=bwd_backend,
            field_offsets=statics["field_offsets"], bank_live=bank_live)
    emb = shard(emb, dist, dp(dist), None, None).astype(cfg.dtype)
    return _ctr_head(cfg, params, dense, emb)


def _ctr_head(cfg: DLRMConfig, params: dict, dense: Array,
              emb: Array) -> Array:
    """The CTR compute after the lookup: bottom MLP over the dense
    features, pairwise dot interaction with the (B, F, D) bags, top MLP.
    Returns logits (B,)."""
    with jax.named_scope("bottom_mlp"):
        x = mlp_apply(params["bot"], dense.astype(cfg.dtype))    # (B, D)
    with jax.named_scope("interaction"):
        z = jnp.concatenate([x[:, None], emb], axis=1)           # (B, F+1, D)
        inter = dot_interaction(z)                               # (B, P)
        feat = jnp.concatenate([inter, x], axis=-1)
    with jax.named_scope("top_mlp"):
        return mlp_apply(params["top"], feat)[:, 0]


def forward_cached(cfg: DLRMConfig, params: dict, statics: dict,
                   cache_table: BankedTable, batch: dict,
                   dist: DistCtx | None = None, *, backend: str = "auto",
                   bwd_backend: str = "auto",
                   remap_bank: Array | None = None,
                   remap_slot: Array | None = None,
                   bank_live: Array | None = None) -> Array:
    """Cache-aware path (Fig. 7): batch carries rewritten multi-hot bags:
    ``cache_idx`` (B, T, Lc) entries into the partial-sum cache table and
    ``residual_idx`` (B, T, Lr) union-vocab rows. Bag sum = cache partials +
    residual rows — ONE fused stage-2 pass over both tables (one psum), then
    identical CTR compute.

    ``remap_bank`` / ``remap_slot`` override the EMT remap vectors in
    ``statics``. The adaptive serve loop passes them (and ``cache_table``) as
    jit ARGUMENTS so a live plan/cache swap feeds new same-shape arrays to
    the already-compiled step — zero recompiles (launch/serve.py
    --adaptive --partition cache_aware)."""
    dense = batch["dense"]
    if remap_bank is not None:
        statics = {**statics, "remap_bank": remap_bank,
                   "remap_slot": remap_slot}
    t = _banked(params, statics)
    emb = banked_cache_residual_bag(t, cache_table, batch["cache_idx"],
                                    batch["residual_idx"], dist,
                                    backend=backend,
                                    bwd_backend=bwd_backend,
                                    bank_live=bank_live)
    return _ctr_head(cfg, params, dense, emb)


def bce_loss(logits: Array, labels: Array) -> Array:
    logits = logits.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels
        + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def loss_fn(cfg: DLRMConfig, params: dict, statics: dict, batch: dict,
            dist: DistCtx | None = None, *, backend: str = "auto",
            bwd_backend: str = "auto", tiered=None) -> Array:
    return bce_loss(forward(cfg, params, statics, batch, dist,
                            backend=backend, bwd_backend=bwd_backend,
                            tiered=tiered),
                    batch["label"])


def retrieval_scores(cfg: DLRMConfig, params: dict, statics: dict,
                     batch: dict, dist: DistCtx | None = None) -> Array:
    """retrieval_cand: one query × N candidate ids for field 0 -> scores (N,).

    Batched-dot formulation: the user side (dense + fields 1..F-1) is computed
    once; candidate embeddings stream through the interaction in a vectorized
    tile, sharded over every mesh axis — never a Python loop.
    """
    dense, sparse, cand = batch["dense"], batch["sparse"], batch["candidates"]
    N = cand.shape[0]
    t = _banked(params, statics)
    x = mlp_apply(params["bot"], dense.astype(cfg.dtype))        # (1, D)
    rows = sparse[:, 1:] + statics["field_offsets"][None, 1:]
    emb_user = banked_gather(t, rows, dist)                      # (1, F-1, D)
    cand_rows = cand + statics["field_offsets"][0]
    emb_cand = banked_gather(t, cand_rows, dist)                 # (N, D)
    if dist is not None:
        from repro.dist.collectives import all_mesh_axes
        emb_cand = shard(emb_cand, dist, all_mesh_axes(dist), None)
    z_user = jnp.concatenate([x[:, None], emb_user], axis=1)     # (1, F, D)
    zu = jnp.broadcast_to(z_user, (N,) + z_user.shape[1:])
    z = jnp.concatenate([zu, emb_cand[:, None]], axis=1)         # (N, F+1, D)
    inter = dot_interaction(z)
    feat = jnp.concatenate([inter, jnp.broadcast_to(x, (N, x.shape[-1]))], -1)
    return mlp_apply(params["top"], feat)[:, 0]
