"""PIMEmbeddingBag: bank-partitioned embedding lookup (the paper's runtime).

The UPMEM dataflow (paper Fig. 4) maps 1:1 onto a ``shard_map`` over the mesh's
``model`` axis (DESIGN.md §2):

  stage 1  indices replicated across the bank axis        (CPU->DPU broadcast)
  stage 2  masked local gather + segment-reduce per bank  (in-DPU lookup+reduce)
  stage 3  psum of partial bag-sums over the bank axis    (DPU->CPU combine)

A table is *packed* by a PartitionPlan (core/partitioning.py): rows are
physically reordered so bank b's rows are contiguous, giving a global
``(n_banks * rows_per_bank, dim)`` array sharded ``P('model', None)`` — each
device holds exactly its bank.  The row->(bank, slot) remap is two replicated
``int32[vocab]`` vectors (8 B/row).

Stage 2 has two interchangeable implementations behind the ``backend`` knob:

  * ``backend='jnp'``    — a segment-scan over the bag length: the accumulator
    is (..., D) and only ONE (..., D) gather lives at a time, so the
    (..., L, D) gathered intermediate of a naive take->mask->sum never
    materializes (the XLA analogue of the paper's in-DPU reduce).
  * ``backend='pallas'`` — the fused TPU kernel (kernels/embedding_bag.py):
    scalar-prefetched indices + remap, double-buffered HBM row DMA, ownership
    mask and per-field offsets applied in-kernel. Off-TPU it runs in
    interpret mode (tests); on TPU it is the production hot path.
  * ``backend='auto'``   — 'pallas' on TPU, 'jnp' elsewhere.

Both run *inside* the shard_map (per bank) and both are differentiable: the
pallas path carries a custom_vjp whose backward is the row scatter-add that is
the exact transpose of the bag sum. The backward has its own backend pair
behind ``bwd_backend`` ('auto' follows the forward): the XLA segment-scan
scatter (``_scatter_bag_ct``), or the Pallas sorted-run scatter kernel
(``kernels/embedding_bag.ct_scatter_bag_pallas``) that keeps the gradient's
irregular row traffic on the same double-buffered near-memory path as the
lookup — a pallas training step never leaves the kernel layer for embedding
traffic.

Column-split mode (the paper's N_c knob) shards the embedding dim instead:
every bank gathers full bags for its dim-slice (no mask, no psum) and stage 3
becomes an all-gather of dim slices — the same Eq. 1 tradeoff with TPU
constants (§Perf explores it).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partitioning import PartitionPlan

Array = jax.Array

BACKENDS = ("auto", "jnp", "pallas", "tuned")


def _resolve_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    if backend == "tuned":
        raise ValueError("backend='tuned' resolves through the dispatch "
                         "cache at the entry points — this path has no "
                         "tuned signature (pass 'auto')")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return backend


def _dispatch(path: str, *, vocab: int, dim: int, batch: int, bag_len,
              n_fields: int = 1, k_max: int = 1, tier_mix: str = "none",
              bwd_backend: str = "auto", tile_b: int,
              n_slots: int | None) -> tuple[str, int, int | None]:
    """Resolve ``backend='tuned'``: look the call signature up in the
    persisted dispatch cache (repro.tune, TUNE_dispatch.json) and return
    (backend, tile_b, n_slots) — the measured decision on a hit, today's
    defaults (the caller's tile_b/n_slots, None left to the kernel, + the
    pre-tuner auto rule) on a miss. Shapes are static under jit, so this
    runs at trace time: a pure host dict lookup, deterministic per shape,
    zero recompiles."""
    from repro.tune.dispatch import decide
    d = decide(path, vocab=vocab, dim=dim, batch=batch, bag_len=bag_len,
               n_fields=n_fields, k_max=k_max, tier_mix=tier_mix,
               bwd_backend=bwd_backend, default_tile_b=tile_b,
               default_n_slots=n_slots)
    return d.backend, d.tile_b, d.n_slots


def _default_interpret(interpret: bool | None) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _check_vma(backend: str, interpret: bool) -> bool:
    """``check_vma`` for a ``jax.shard_map`` whose body runs stage 2 on
    ``backend``. JAX's Pallas HLO interpreter evaluates a kernel body
    without varying-axis types, which the check rejects, so it is off only
    around an interpreted kernel: the compiled kernel and the jnp path keep
    it on."""
    return not (backend == "pallas" and interpret)


def _resolve_bwd(bwd_backend: str, fwd_backend: str) -> str:
    """Backward scatter backend: 'auto' rides the (resolved) forward choice,
    so ``backend='pallas'`` alone puts fwd AND bwd near memory; 'jnp' forces
    the XLA scatter fallback under a pallas forward (the parity baseline).
    Only consulted on the pallas forward — the jnp forward differentiates
    through its scan natively."""
    if bwd_backend not in BACKENDS or bwd_backend == "tuned":
        raise ValueError(f"bwd_backend must be one of "
                         f"{tuple(b for b in BACKENDS if b != 'tuned')}, "
                         f"got {bwd_backend!r} (the tuned dispatch keys on "
                         f"bwd_backend; it does not select one)")
    return fwd_backend if bwd_backend == "auto" else bwd_backend


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BankedTable:
    """Pytree: packed rows + remap. ``packed`` shards P(bank_axis, None)."""

    packed: Array       # (n_banks * rows_per_bank, dim)
    remap_bank: Array   # (vocab,) int32, replicated
    remap_slot: Array   # (vocab,) int32, replicated
    n_banks: int = dataclasses.field(metadata=dict(static=True))
    rows_per_bank: int = dataclasses.field(metadata=dict(static=True))

    @property
    def vocab(self) -> int:
        return self.remap_bank.shape[0]

    @property
    def dim(self) -> int:
        return self.packed.shape[-1]

    def flat_remap(self) -> Array:
        """row -> position in the unsharded packed array."""
        return (self.remap_bank * self.rows_per_bank
                + self.remap_slot).astype(jnp.int32)


def pack_table(table: np.ndarray, plan: PartitionPlan,
               dtype=None) -> BankedTable:
    """Physically reorder rows by the plan; pad banks to a common row count."""
    vocab, dim = table.shape
    rows_per_bank = int(plan.max_rows_per_bank)
    packed = np.zeros((plan.n_banks * rows_per_bank, dim), dtype=table.dtype)
    flat_pos = plan.bank_of_row.astype(np.int64) * rows_per_bank + plan.slot_of_row
    packed[flat_pos] = table
    if dtype is not None:
        packed = packed.astype(dtype)
    return BankedTable(
        packed=jnp.asarray(packed),
        remap_bank=jnp.asarray(plan.bank_of_row, dtype=jnp.int32),
        remap_slot=jnp.asarray(plan.slot_of_row, dtype=jnp.int32),
        n_banks=plan.n_banks,
        rows_per_bank=rows_per_bank,
    )


def init_banked(key, plan: PartitionPlan, dim: int, *, scale: float = 0.01,
                dtype=jnp.float32) -> BankedTable:
    """Random-init a banked table without materializing the unpacked layout."""
    rows_per_bank = int(plan.max_rows_per_bank)
    packed = jax.random.normal(
        key, (plan.n_banks * rows_per_bank, dim), dtype) * scale
    return BankedTable(
        packed=packed,
        remap_bank=jnp.asarray(plan.bank_of_row, dtype=jnp.int32),
        remap_slot=jnp.asarray(plan.slot_of_row, dtype=jnp.int32),
        n_banks=plan.n_banks,
        rows_per_bank=rows_per_bank,
    )


# ---------------------------------------------------------------------------
# replicated table: hot rows live on k banks, a hash splits their traffic
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ReplicatedTable:
    """Pytree: packed rows + replica-axis remap (core/partitioning.py
    ``ReplicatedPlan``). ``remap_bank``/``remap_slot`` are ``(vocab, k_max)``
    with cyclic-padded columns, so any column of row v is a valid copy; the
    lookup picks column ``wang_hash(bag) % k_max`` per bag. ``k_max == 1``
    (or a plan with no replicated rows) is layout-identical to
    ``BankedTable``.
    """

    packed: Array       # (n_banks * rows_per_bank, dim)
    remap_bank: Array   # (vocab, k_max) int32, replicated
    remap_slot: Array   # (vocab, k_max) int32, replicated
    n_banks: int = dataclasses.field(metadata=dict(static=True))
    rows_per_bank: int = dataclasses.field(metadata=dict(static=True))
    k_max: int = dataclasses.field(default=1, metadata=dict(static=True))

    @property
    def vocab(self) -> int:
        return self.remap_bank.shape[0]

    @property
    def dim(self) -> int:
        return self.packed.shape[-1]

    def flat_remap(self) -> Array:
        """(vocab * k_max,) copy -> position in the unsharded packed array —
        the flattened stream the kernel indexes at ``row * k_max + r``."""
        return (self.remap_bank * self.rows_per_bank
                + self.remap_slot).reshape(-1).astype(jnp.int32)

    def flat_bank(self) -> Array:
        """(vocab * k_max,) int32 bank per copy, kernel-stream order."""
        return self.remap_bank.reshape(-1).astype(jnp.int32)


def pack_replicated(table: np.ndarray, rplan, *,
                    rows_per_bank: int | None = None,
                    dtype=None) -> ReplicatedTable:
    """Physically materialize every copy the plan calls for: row v is
    written to all ``copies[v]`` of its (bank, slot) homes."""
    vocab, dim = table.shape
    if rows_per_bank is None:
        rows_per_bank = int(rplan.max_rows_per_bank)
    packed = np.zeros((rplan.n_banks * rows_per_bank, dim), dtype=table.dtype)
    vv, rr = np.nonzero(np.arange(rplan.k_max)[None, :]
                        < rplan.copies[:, None])
    pos = (rplan.bank_of_copy[vv, rr].astype(np.int64) * rows_per_bank
           + rplan.slot_of_copy[vv, rr])
    packed[pos] = table[vv]
    if dtype is not None:
        packed = packed.astype(dtype)
    return ReplicatedTable(
        packed=jnp.asarray(packed),
        remap_bank=jnp.asarray(rplan.bank_of_copy, dtype=jnp.int32),
        remap_slot=jnp.asarray(rplan.slot_of_copy, dtype=jnp.int32),
        n_banks=rplan.n_banks,
        rows_per_bank=rows_per_bank,
        k_max=rplan.k_max,
    )


# ---------------------------------------------------------------------------
# stage 2, jnp backend: segment-scan over the bag length
# ---------------------------------------------------------------------------

def _carry_like(init: Array, *refs) -> Array:
    """A scan carry's initial value, varying over every manual mesh axis
    the ``refs`` vary over. Inside ``shard_map`` a carry must enter with
    the type the body returns, and a body that reads bank-local rows or
    batch-sharded ids returns a varying value. Outside, a no-op."""
    axes = set()
    for r in refs:
        if r is not None:
            axes |= set(jax.typeof(r).vma)
    if not axes:
        return init
    return jax.lax.pcast(init, tuple(sorted(axes)), to="varying")


def _field_offsets_per_bag(off: Array, n: int) -> Array:
    """Bag n of a flattened (..., F, L) batch belongs to field n % F."""
    return off[jnp.arange(n, dtype=jnp.int32) % off.shape[0]]


def _bag_partial_scan(table: Array, idx: Array, *, remap: Array | None,
                      bank: Array | None, my_bank, off: Array) -> Array:
    """Bag sums over the trailing L WITHOUT a (..., L, D) intermediate.

    Scans the bag length, accumulating one (N, D) gather at a time — the jnp
    rendition of the kernel's streaming accumulate. ``remap`` maps global rows
    to local slots (identity when None); ``bank``/``my_bank`` apply the PIM
    ownership mask (skipped when bank is None); ``off`` is the per-field
    offset vector ((1,) zeros when fields are pre-offset).
    """
    lead, L = idx.shape[:-1], idx.shape[-1]
    flat = idx.reshape(-1, L)
    N = flat.shape[0]
    offs = _field_offsets_per_bag(off, N)
    dim = table.shape[-1]

    def body(acc, j):
        raw = flat[:, j]
        valid = raw >= 0
        row = jnp.where(valid, raw + offs, 0)
        if bank is None:
            mine = valid
        else:
            mine = valid & (bank[row] == my_bank)
        src = row if remap is None else remap[row]
        rows = jnp.take(table, jnp.where(mine, src, 0), axis=0)
        return acc + jnp.where(mine[:, None], rows, 0).astype(acc.dtype), None

    init = _carry_like(jnp.zeros((N, dim), jnp.float32), table, idx, remap,
                       bank, my_bank, off)
    acc, _ = jax.lax.scan(body, init, jnp.arange(L))
    return acc.reshape(*lead, dim).astype(table.dtype)


def _local_gather_partial(table_local: Array, bank: Array, slot: Array,
                          idx: Array, my_bank: Array) -> Array:
    """Dense (non-reducing) lookup partial: (...,) idx -> (..., dim)."""
    safe = jnp.where(idx >= 0, idx, 0)
    owner = bank[safe]
    s = slot[safe]
    mine = (idx >= 0) & (owner == my_bank)
    rows = jnp.take(table_local, jnp.where(mine, s, 0), axis=0)
    return jnp.where(mine[..., None], rows, 0)


# ---------------------------------------------------------------------------
# stage 2, pallas backend: fused kernel + scatter-add custom_vjp
# ---------------------------------------------------------------------------

def _pad_bags(flat: Array, tile_b: int) -> tuple[Array, int]:
    from repro.kernels.embedding_bag import pad_leading
    return pad_leading(flat, tile_b)


@jax.named_scope("relayout")
def _pad_lanes(table: Array, interpret: bool) -> tuple[Array, int]:
    if interpret:               # no lane constraint off-TPU: skip the copy
        return table, table.shape[-1]
    from repro.kernels.embedding_bag import pad_last_dim
    return pad_last_dim(table)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pallas_bag(cfg: tuple, packed: Array, bank: Array, slot: Array,
                off: Array, my: Array, idx: Array) -> Array:
    """One bank's stage-2 partial bag sums via the fused Pallas kernel.

    cfg = (tile_b, interpret, bwd, n_slots). idx (..., L) raw per-field ids;
    bank/slot the replicated remap; my () int32 bank id (< 0: own everything
    — the unsharded path, where slot is the flat remap). ``bwd`` selects the
    custom_vjp backward: 'pallas' = the sorted-run scatter kernel, 'jnp' =
    the XLA segment-scan scatter. ``n_slots`` is the row-DMA pipeline depth
    (fwd and bwd kernels alike; None: each kernel's own default).
    """
    from repro.kernels.embedding_bag import banked_embedding_bag_pallas
    tile_b, interpret, _, n_slots = cfg
    lead, L = idx.shape[:-1], idx.shape[-1]
    flat, n = _pad_bags(idx.reshape(-1, L).astype(jnp.int32), tile_b)
    out = banked_embedding_bag_pallas(
        packed, bank, slot, off, my.reshape(1).astype(jnp.int32), flat,
        tile_b=tile_b, interpret=interpret, n_slots=n_slots)
    return out[:n].reshape(*lead, packed.shape[-1])


def _pallas_bag_fwd(cfg, packed, bank, slot, off, my, idx):
    return _pallas_bag(cfg, packed, bank, slot, off, my, idx), \
        (packed, bank, slot, off, my, idx)


def _pallas_bag_bwd(cfg, res, ct):
    tile_b, interpret, bwd, n_slots = cfg
    packed, bank, slot, off, my, idx = res
    if bwd == "pallas":
        from repro.kernels.embedding_bag import ct_scatter_bag_pallas
        L = idx.shape[-1]
        d_tab = ct_scatter_bag_pallas(
            ct.reshape(-1, ct.shape[-1]),
            idx.reshape(-1, L).astype(jnp.int32), bank, slot, off,
            my.reshape(1).astype(jnp.int32), packed.shape[0], packed.dtype,
            tile_s=tile_b, interpret=interpret, n_slots=n_slots)
    else:
        d_tab = _scatter_bag_ct(packed.shape, packed.dtype, bank, slot, my,
                                idx, ct, off=off)
    return (d_tab, None, None, None, None, None)


_pallas_bag.defvjp(_pallas_bag_fwd, _pallas_bag_bwd)


# ---------------------------------------------------------------------------
# replicated stage 2: hash-picked replica per bag, k-way gradient scatter
# ---------------------------------------------------------------------------

def _replica_cols(n: int, k_max: int) -> Array:
    """Replica column per flattened bag — the SAME ``wang_hash(bag) % k``
    pick the kernel makes (kernels.embedding_bag.replica_of_bag), so jnp
    and pallas read identical copies."""
    from repro.kernels.embedding_bag import replica_of_bag
    return replica_of_bag(jnp.arange(n, dtype=jnp.int32), k_max)


def _replicated_bag_scan(table: Array, idx: Array, *, bank_flat: Array,
                         slot_flat: Array, my_bank, off: Array,
                         k_max: int) -> Array:
    """jnp fallback for the replicated stage 2: ``_bag_partial_scan``'s
    dataflow with the per-bag replica column folded into the remap index.
    Same j-ascending fp32 accumulation, so it bit-matches the kernel."""
    lead, L = idx.shape[:-1], idx.shape[-1]
    flat = idx.reshape(-1, L)
    N = flat.shape[0]
    offs = _field_offsets_per_bag(off, N)
    rcol = _replica_cols(N, k_max)
    dim = table.shape[-1]

    def body(acc, j):
        raw = flat[:, j]
        valid = raw >= 0
        row = jnp.where(valid, raw + offs, 0)
        rowk = row * k_max + rcol if k_max > 1 else row
        mine = valid & ((my_bank < 0) | (bank_flat[rowk] == my_bank))
        src = jnp.where(mine, slot_flat[rowk], 0)
        rows = jnp.take(table, src, axis=0)
        return acc + jnp.where(mine[:, None], rows, 0).astype(acc.dtype), None

    init = _carry_like(jnp.zeros((N, dim), jnp.float32), table, idx,
                       bank_flat, slot_flat, my_bank, off)
    acc, _ = jax.lax.scan(body, init, jnp.arange(L))
    return acc.reshape(*lead, dim).astype(table.dtype)


def _replicated_scatter_ct(shape, dtype, bank_flat, slot_flat, my, idx, ct,
                           *, off, k_max: int):
    """Transpose of the replicated bag sum (jnp): each entry's cotangent
    lands on the copy its forward read came through, so a row's copies
    together receive exactly the single-copy gradient."""
    L = idx.shape[-1]
    flat = idx.reshape(-1, L)
    N = flat.shape[0]
    ctf = ct.reshape(N, -1).astype(jnp.float32)
    offs = _field_offsets_per_bag(off, N)
    rcol = _replica_cols(N, k_max)

    def body(d_tab, j):
        raw = flat[:, j]
        valid = raw >= 0
        row = jnp.where(valid, raw + offs, 0)
        rowk = row * k_max + rcol if k_max > 1 else row
        mine = valid & ((my < 0) | (bank_flat[rowk] == my))
        src = jnp.where(mine, slot_flat[rowk], 0)
        upd = jnp.where(mine[:, None], ctf, 0)
        return d_tab.at[src].add(upd), None

    init = _carry_like(jnp.zeros(shape, jnp.float32), bank_flat, slot_flat,
                       my, idx, ct, off)
    d_tab, _ = jax.lax.scan(body, init, jnp.arange(L))
    return d_tab.astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _replicated_bag(cfg: tuple, packed: Array, bank_flat: Array,
                    slot_flat: Array, off: Array, my: Array,
                    idx: Array) -> Array:
    """Stage-2 partial bag sums over a REPLICATED table.

    cfg = (tile_b, interpret, backend, bwd, k_max, n_slots). bank_flat/
    slot_flat are the flattened (vocab * k_max,) replica-axis remap; each
    bag reads copy ``wang_hash(bag) % k_max``. The pallas path is the
    ordinary banked kernel with ``k_max`` folded into its entry resolver.
    """
    tile_b, interpret, backend, _, k_max, n_slots = cfg
    if backend == "pallas":
        from repro.kernels.embedding_bag import banked_embedding_bag_pallas
        lead, L = idx.shape[:-1], idx.shape[-1]
        flat, n = _pad_bags(idx.reshape(-1, L).astype(jnp.int32), tile_b)
        out = banked_embedding_bag_pallas(
            packed, bank_flat, slot_flat, off,
            my.reshape(1).astype(jnp.int32), flat,
            tile_b=tile_b, interpret=interpret, k_max=k_max,
            n_slots=n_slots)
        return out[:n].reshape(*lead, packed.shape[-1])
    return _replicated_bag_scan(packed, idx, bank_flat=bank_flat,
                                slot_flat=slot_flat, my_bank=my, off=off,
                                k_max=k_max)


def _replicated_bag_fwd(cfg, packed, bank_flat, slot_flat, off, my, idx):
    out = _replicated_bag(cfg, packed, bank_flat, slot_flat, off, my, idx)
    return out, (packed, bank_flat, slot_flat, off, my, idx)


def _replicated_bag_bwd(cfg, res, ct):
    tile_b, interpret, _, bwd, k_max, n_slots = cfg
    packed, bank_flat, slot_flat, off, my, idx = res
    if bwd == "pallas":
        from repro.kernels.embedding_bag import ct_scatter_bag_pallas
        L = idx.shape[-1]
        d_tab = ct_scatter_bag_pallas(
            ct.reshape(-1, ct.shape[-1]),
            idx.reshape(-1, L).astype(jnp.int32), bank_flat, slot_flat, off,
            my.reshape(1).astype(jnp.int32), packed.shape[0], packed.dtype,
            tile_s=tile_b, interpret=interpret, k_max=k_max,
            n_slots=n_slots)
    else:
        d_tab = _replicated_scatter_ct(packed.shape, packed.dtype, bank_flat,
                                       slot_flat, my, idx, ct, off=off,
                                       k_max=k_max)
    return (d_tab, None, None, None, None, None)


_replicated_bag.defvjp(_replicated_bag_fwd, _replicated_bag_bwd)


# ---------------------------------------------------------------------------
# tiered stage 2: in-kernel dequant forward, straight-through backward
# ---------------------------------------------------------------------------

def _tiered_partial_scan(payload: Array, scale: Array, tier: Array,
                         idx: Array, *, remap: Array, bank: Array, my_bank,
                         off: Array, dim: int, hot_dtype: str) -> Array:
    """jnp fallback for the tiered stage 2: the ``_bag_partial_scan``
    dataflow with the quant package's shared fp32 dequant applied to each
    gathered byte row. Per bag, entries accumulate in the same j-ascending
    fp32 order as the kernel's walk, so the two backends bit-match."""
    from repro.quant.quantize import dequant_rows_f32
    lead, L = idx.shape[:-1], idx.shape[-1]
    flat = idx.reshape(-1, L)
    N = flat.shape[0]
    offs = _field_offsets_per_bag(off, N)

    def body(acc, j):
        raw = flat[:, j]
        valid = raw >= 0
        row = jnp.where(valid, raw + offs, 0)
        mine = valid & ((my_bank < 0) | (bank[row] == my_bank))
        src = jnp.where(mine, remap[row], 0)
        rows = dequant_rows_f32(jnp.take(payload, src, axis=0),
                                jnp.take(scale, src), jnp.take(tier, src),
                                dim, hot_dtype)
        return acc + jnp.where(mine[:, None], rows, 0.0), None

    init = _carry_like(jnp.zeros((N, dim), jnp.float32), payload, scale,
                       tier, idx, remap, bank, my_bank, off)
    acc, _ = jax.lax.scan(body, init, jnp.arange(L))
    return acc.reshape(*lead, dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _tiered_bag(cfg: tuple, fp_packed: Array, payload: Array,
                scale_bits: Array, tier: Array, bank: Array, slot: Array,
                off: Array, my: Array, idx: Array) -> Array:
    """One bank's tiered stage-2 partial bag sums (fp32).

    cfg = (tile_b, interpret, backend, bwd, dim, hot_dtype, n_slots). The
    forward
    reads ONLY the quantized payload (dequant in-kernel / in-scan);
    ``fp_packed`` — the fp master table the payload was quantized from — is
    the STRAIGHT-THROUGH gradient carrier: the backward scatters the bag
    cotangents onto it exactly like the full-precision lookup's backward,
    so training through mixed tiers updates fp rows as if the lookup had
    been full-precision (quantized rows included).
    """
    tile_b, interpret, backend, _, dim, hot, n_slots = cfg
    if backend == "pallas":
        from repro.kernels.embedding_bag import tiered_embedding_bag_pallas
        lead, L = idx.shape[:-1], idx.shape[-1]
        flat, n = _pad_bags(idx.reshape(-1, L).astype(jnp.int32), tile_b)
        pay, _ = _pad_lanes(payload, interpret)
        out = tiered_embedding_bag_pallas(
            pay, scale_bits, tier, bank, slot, off,
            my.reshape(1).astype(jnp.int32), flat, dim=dim, hot_dtype=hot,
            tile_b=tile_b, interpret=interpret, n_slots=n_slots)
        return out[:n].reshape(*lead, dim)
    scale = jax.lax.bitcast_convert_type(scale_bits, jnp.float32)
    return _tiered_partial_scan(payload, scale, tier, idx, remap=slot,
                                bank=bank, my_bank=my, off=off, dim=dim,
                                hot_dtype=hot)


def _tiered_bag_fwd(cfg, fp_packed, payload, scale_bits, tier, bank, slot,
                    off, my, idx):
    out = _tiered_bag(cfg, fp_packed, payload, scale_bits, tier, bank, slot,
                      off, my, idx)
    return out, (fp_packed, bank, slot, off, my, idx)


def _tiered_bag_bwd(cfg, res, ct):
    tile_b, interpret, _, bwd, _, _, n_slots = cfg
    fp_packed, bank, slot, off, my, idx = res
    if bwd == "pallas":
        from repro.kernels.embedding_bag import ct_scatter_bag_pallas
        L = idx.shape[-1]
        d_tab = ct_scatter_bag_pallas(
            ct.reshape(-1, ct.shape[-1]),
            idx.reshape(-1, L).astype(jnp.int32), bank, slot, off,
            my.reshape(1).astype(jnp.int32), fp_packed.shape[0],
            fp_packed.dtype, tile_s=tile_b, interpret=interpret,
            n_slots=n_slots)
    else:
        d_tab = _scatter_bag_ct(fp_packed.shape, fp_packed.dtype, bank, slot,
                                my, idx, ct, off=off)
    return (d_tab, None, None, None, None, None, None, None, None)


_tiered_bag.defvjp(_tiered_bag_fwd, _tiered_bag_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pallas_cache_bag(cfg: tuple, emt_packed: Array, cache_packed: Array,
                      e_bank: Array, e_slot: Array, c_bank: Array,
                      c_slot: Array, my: Array, cache_idx: Array,
                      resid_idx: Array) -> Array:
    """Fused Fig.-7 stage 2: Σ cache partials + Σ residual rows, one kernel.
    cfg = (tile_b, interpret, bwd, n_slots)."""
    from repro.kernels.embedding_bag import fused_cache_bag_pallas
    tile_b, interpret, _, n_slots = cfg
    lead = cache_idx.shape[:-1]
    ci, n = _pad_bags(cache_idx.reshape(-1, cache_idx.shape[-1])
                      .astype(jnp.int32), tile_b)
    ri, _ = _pad_bags(resid_idx.reshape(-1, resid_idx.shape[-1])
                      .astype(jnp.int32), tile_b)
    emt, d = _pad_lanes(emt_packed, interpret)
    cache, _ = _pad_lanes(cache_packed, interpret)
    out = fused_cache_bag_pallas(
        emt, cache, e_bank, e_slot, c_bank, c_slot,
        my.reshape(1).astype(jnp.int32), ci, ri,
        tile_b=tile_b, interpret=interpret, n_slots=n_slots)
    return out[:n, :d].reshape(*lead, d)


def _pallas_cache_bag_fwd(cfg, emt_packed, cache_packed, e_bank, e_slot,
                          c_bank, c_slot, my, cache_idx, resid_idx):
    out = _pallas_cache_bag(cfg, emt_packed, cache_packed, e_bank, e_slot,
                            c_bank, c_slot, my, cache_idx, resid_idx)
    return out, (emt_packed, cache_packed, e_bank, e_slot, c_bank, c_slot,
                 my, cache_idx, resid_idx)


def _scatter_bag_ct(shape, dtype, bank, slot, my, idx, ct, *, off=None):
    """Transpose of the bag sum: scatter ct rows back onto owned slots.

    Scans L like the forward, so the update buffer is one (N, D) slab — the
    (N*L, D) updates tensor of a flat scatter never materializes. Accumulates
    in fp32 regardless of the table dtype (thousands of colliding adds onto
    hot rows would round to zero in a bf16 accumulator), casting to the table
    dtype at the end — same policy as the kernels' forward accumulator.
    """
    L = idx.shape[-1]
    flat = idx.reshape(-1, L)
    N = flat.shape[0]
    ctf = ct.reshape(N, -1).astype(jnp.float32)
    offs = None if off is None else _field_offsets_per_bag(off, N)

    def body(d_tab, j):
        raw = flat[:, j]
        valid = raw >= 0
        row = jnp.where(valid, raw if offs is None else raw + offs, 0)
        mine = valid & ((my < 0) | (bank[row] == my))
        src = jnp.where(mine, slot[row], 0)
        upd = jnp.where(mine[:, None], ctf, 0)
        return d_tab.at[src].add(upd), None

    init = _carry_like(jnp.zeros(shape, jnp.float32), bank, slot, my, idx,
                       ct, off)
    d_tab, _ = jax.lax.scan(body, init, jnp.arange(L))
    return d_tab.astype(dtype)


def _pallas_cache_bag_bwd(cfg, res, ct):
    tile_b, interpret, bwd, n_slots = cfg
    (emt_packed, cache_packed, e_bank, e_slot, c_bank, c_slot, my,
     cache_idx, resid_idx) = res
    if bwd == "pallas":
        # dual scatter: the fused forward summed BOTH streams into one bag
        # row, so the same cotangent scatters onto the EMT (via the residual
        # ids) and the cache table (via the cache ids) — two invocations of
        # the sorted-run kernel, one per destination table
        from repro.kernels.embedding_bag import ct_scatter_bag_pallas
        ctf = ct.reshape(-1, ct.shape[-1])
        zero = jnp.zeros((1,), jnp.int32)
        myk = my.reshape(1).astype(jnp.int32)
        d_emt = ct_scatter_bag_pallas(
            ctf, resid_idx.reshape(-1, resid_idx.shape[-1]).astype(jnp.int32),
            e_bank, e_slot, zero, myk, emt_packed.shape[0], emt_packed.dtype,
            tile_s=tile_b, interpret=interpret, n_slots=n_slots)
        d_cache = ct_scatter_bag_pallas(
            ctf, cache_idx.reshape(-1, cache_idx.shape[-1]).astype(jnp.int32),
            c_bank, c_slot, zero, myk, cache_packed.shape[0],
            cache_packed.dtype, tile_s=tile_b, interpret=interpret,
            n_slots=n_slots)
    else:
        d_emt = _scatter_bag_ct(emt_packed.shape, emt_packed.dtype,
                                e_bank, e_slot, my, resid_idx, ct)
        d_cache = _scatter_bag_ct(cache_packed.shape, cache_packed.dtype,
                                  c_bank, c_slot, my, cache_idx, ct)
    return (d_emt, d_cache, None, None, None, None, None, None, None)


_pallas_cache_bag.defvjp(_pallas_cache_bag_fwd, _pallas_cache_bag_bwd)


# ---------------------------------------------------------------------------
# single-device semantics
# ---------------------------------------------------------------------------

def lookup_unsharded(t: BankedTable, idx: Array, *, reduce_bag: bool,
                     field_offsets: Array | None = None) -> Array:
    """Single-device semantics (CPU path + oracle), scan formulation."""
    off = jnp.zeros((1,), jnp.int32) if field_offsets is None \
        else jnp.asarray(field_offsets, jnp.int32)
    if reduce_bag:
        return _bag_partial_scan(t.packed, idx, remap=t.flat_remap(),
                                 bank=None, my_bank=None, off=off)
    assert field_offsets is None, "dense gather expects pre-offset rows"
    safe = jnp.where(idx >= 0, idx, 0)
    rows = jnp.take(t.packed, t.flat_remap()[safe], axis=0)
    return jnp.where((idx >= 0)[..., None], rows, 0)


# ---------------------------------------------------------------------------
# bounded-degraded reads: the per-bank liveness mask
# ---------------------------------------------------------------------------

def _effective_bank_map(remap_bank: Array, bank_live: Array,
                        n_banks: int) -> Array:
    """Rewrite the row->bank map so DEAD banks own nothing: rows homed on a
    dead bank get bank id ``n_banks``, which no ``axis_index`` ever matches —
    their contribution to the psum is exactly zero (the zero-fill degraded
    substitute), with NO kernel or shard_map changes. ``bank_live`` is a
    (n_banks,) bool jit ARGUMENT, so flipping a bank dead/alive between
    micro-batches is a pure argument change against one executable (the same
    zero-recompile contract as the remap vectors)."""
    return jnp.where(bank_live[remap_bank], remap_bank,
                     jnp.int32(n_banks)).astype(jnp.int32)


def _binary_live_map(remap_bank: Array, bank_live: Array) -> Array:
    """Unsharded rendition of the same trick: the single-device path owns
    everything via ``my_bank < 0``, which would bypass a bank-map mask — so
    degraded single-device lookups pass ``my_bank = 0`` against a binary map
    (0 = row's bank alive, 1 = dead). Ownership machinery unchanged on both
    backends."""
    return jnp.where(bank_live[remap_bank], 0, 1).astype(jnp.int32)


def degraded_row_counts(remap_bank: Array, bank_live: Array, rows: Array,
                        *, per_bag: bool = False) -> Array:
    """Count of reads that resolved to a dead bank.

    ``rows``: union-vocab row ids of any shape ``(B, ...)`` (negatives =
    padding). Returns ``(B,)`` int32 by default — the per-request
    ``degraded_read_count`` surfaced per batch so correctness is *boundedly*
    degraded, never silently wrong: a request with count 0 is bit-exact, a
    request with count k is missing exactly k row contributions.
    ``per_bag=True`` sums only the trailing (bag) axis instead — shape
    ``rows.shape[:-1]``, the granularity ``degraded_mean_fill`` needs.

    ``remap_bank`` may also be a replicated ``(vocab, k_max)`` map: a read
    then counts as degraded only when EVERY copy of its row is dead — any
    surviving replica serves it loss-free (``_replica_failover_maps``).
    """
    valid = rows >= 0
    safe = jnp.where(valid, rows, 0)
    live = bank_live[remap_bank[safe]]
    if remap_bank.ndim == 2:
        live = live.any(axis=-1)
    dead = valid & ~live
    if per_bag:
        return dead.sum(axis=-1).astype(jnp.int32)
    return dead.reshape(rows.shape[0], -1).sum(axis=-1).astype(jnp.int32)


def degraded_mean_fill(emb: Array, per_bag_counts: Array,
                       fallback_row: Array) -> Array:
    """Optional mean-fill substitute: add ``fallback_row`` (e.g. the table's
    mean row) once per dead read instead of the implicit zero row.
    ``per_bag_counts`` has ``emb``'s leading shape (``degraded_row_counts``
    with ``per_bag=True``). Applied OUTSIDE the bank collective — inside the
    shard_map every bank would add it and the psum would count it n_banks
    times."""
    return emb + per_bag_counts[..., None].astype(emb.dtype) * fallback_row


# ---------------------------------------------------------------------------
# measured traffic: union-vocab rows for the per-bank counters
# ---------------------------------------------------------------------------

def _traffic_rows(idx: Array, field_offsets: Array | None) -> Array:
    """The union-vocab row ids a batch actually reads: ``field_offsets``
    applied per flattened bag (bag n -> field n % F, exactly the
    ``_field_offsets_per_bag`` rule the lookup paths use), padding kept as
    -1. This is what the ``with_traffic`` counters count."""
    if field_offsets is None:
        return idx
    off = jnp.asarray(field_offsets, jnp.int32)
    flat = idx.reshape(-1, idx.shape[-1])
    offs = _field_offsets_per_bag(off, flat.shape[0])
    return jnp.where(flat >= 0, flat + offs[:, None], -1)


# ---------------------------------------------------------------------------
# distributed lookup
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DistCtx:
    """Mesh context threaded through model code. None => single-device."""

    mesh: jax.sharding.Mesh
    dp_axes: tuple[str, ...]     # batch-sharded axes, e.g. ('pod', 'data')
    bank_axis: str = "model"

    @property
    def n_banks(self) -> int:
        return self.mesh.shape[self.bank_axis]

    def dp_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.dp_axes]))


@jax.named_scope("lookup")
def banked_embedding_bag(t: BankedTable, idx: Array, dist: DistCtx | None,
                         *, reduce_bag: bool = True, backend: str = "auto",
                         bwd_backend: str = "auto",
                         field_offsets: Array | None = None,
                         tile_b: int = 8, n_slots: int | None = None,
                         interpret: bool | None = None,
                         bank_live: Array | None = None,
                         with_traffic: bool = False):
    """The paper's stages 1-3. idx (..., L) -> (..., dim) [reduce] or
    (..., L, dim).

    ``field_offsets`` fuses all F fields of a (B, F, L) multi-hot batch into
    one stage-2 pass: bag (b, f) looks up ``idx + field_offsets[f]`` (applied
    in-kernel / in-scan, only to valid entries).

    ``bwd_backend`` selects the pallas forward's gradient scatter ('auto'
    follows ``backend``): 'pallas' keeps the backward's row traffic on the
    near-memory kernel path, 'jnp' is the XLA scatter fallback.

    ``bank_live`` ((n_banks,) bool, optional) is the degraded-serving mask:
    reads homed on a False bank resolve to the zero row (bounded degradation,
    see ``degraded_row_counts``). It rides as a jit ARGUMENT — the effective
    bank map is recomputed per call, so flipping a bank dead/alive never
    recompiles and needs no kernel changes.

    Under a mesh: shard_map over (dp_axes + bank_axis); indices are sharded on
    batch, replicated across banks (stage 1); each bank computes its partial
    with the selected ``backend`` (stage 2); psum over the bank axis (stage 3).

    ``n_slots`` is the pallas kernel's row-copy ring depth; None leaves it
    to the kernel (``kernels.embedding_bag.bag_ring_depth``).
    ``backend='tuned'`` resolves (backend, tile_b, n_slots) through the
    persisted dispatch cache at trace time (repro.tune); a cache miss is the
    deterministic 'auto' default with the caller's tile_b/n_slots.

    ``with_traffic=True`` additionally returns a ``BankTraffic`` of exact
    per-bank measured read/byte counts for this batch — pure jnp on the
    same jit arguments (the ``degraded_row_counts`` pattern: zero extra
    executables, swap-safe). Return becomes ``(out, traffic)``.
    """
    if with_traffic:
        out = banked_embedding_bag(
            t, idx, dist, reduce_bag=reduce_bag, backend=backend,
            bwd_backend=bwd_backend, field_offsets=field_offsets,
            tile_b=tile_b, n_slots=n_slots, interpret=interpret,
            bank_live=bank_live)
        from repro.obs.traffic import bank_read_counts, traffic_from_reads
        reads = bank_read_counts(t.remap_bank,
                                 _traffic_rows(idx, field_offsets),
                                 t.n_banks, bank_live=bank_live)
        row_nbytes = t.packed.shape[-1] * np.dtype(t.packed.dtype).itemsize
        return out, traffic_from_reads(reads, row_nbytes)
    if backend == "tuned" and reduce_bag:
        backend, tile_b, n_slots = _dispatch(
            "plain", vocab=t.vocab, dim=t.dim,
            batch=int(np.prod(idx.shape[:-1])), bag_len=idx.shape[-1],
            n_fields=1 if field_offsets is None
            else int(np.shape(field_offsets)[0]),
            bwd_backend=bwd_backend, tile_b=tile_b, n_slots=n_slots)
    elif backend == "tuned":
        backend = "auto"        # dense gather: no kernel to tune
    backend = _resolve_backend(backend)
    bwd = _resolve_bwd(bwd_backend, backend)
    interpret = _default_interpret(interpret)
    if not reduce_bag and field_offsets is not None:
        raise ValueError("field_offsets requires reduce_bag=True — the dense "
                         "gather path expects pre-offset union-vocab rows")
    off = jnp.zeros((1,), jnp.int32) if field_offsets is None \
        else jnp.asarray(field_offsets, jnp.int32)

    if dist is None:
        if not reduce_bag:
            out = lookup_unsharded(t, idx, reduce_bag=False)
            if bank_live is not None:
                safe = jnp.where(idx >= 0, idx, 0)
                out = jnp.where(bank_live[t.remap_bank[safe]][..., None],
                                out, 0)
            return out
        if bank_live is None:
            bank_map, my = t.remap_bank, jnp.full((), -1, jnp.int32)
        else:
            bank_map = _binary_live_map(t.remap_bank, bank_live)
            my = jnp.zeros((), jnp.int32)
        if backend == "pallas":
            return _pallas_bag((tile_b, interpret, bwd, n_slots), t.packed,
                               bank_map, t.flat_remap(), off, my, idx)
        return _bag_partial_scan(
            t.packed, idx, remap=t.flat_remap(),
            bank=None if bank_live is None else bank_map,
            my_bank=None if bank_live is None else my, off=off)

    P = jax.sharding.PartitionSpec
    # batch shards over dp when divisible; tiny/odd batches (retrieval's B=1
    # query) replicate across dp instead
    dp_ok = idx.shape[0] % dist.dp_size() == 0
    dp = (dist.dp_axes if len(dist.dp_axes) > 1 else dist.dp_axes[0]) \
        if dp_ok else None
    bank = dist.bank_axis
    idx_spec = P(dp, *([None] * (idx.ndim - 1)))
    out_spec = P(dp, *([None] * (idx.ndim - (1 if reduce_bag else 0))))

    def fn(packed_local, bank_map, slot_map, off_local, idx_local):
        my = jax.lax.axis_index(bank)
        if not reduce_bag:
            part = _local_gather_partial(packed_local, bank_map, slot_map,
                                         idx_local, my)
        elif backend == "pallas":
            part = _pallas_bag((tile_b, interpret, bwd, n_slots),
                               packed_local, bank_map, slot_map, off_local,
                               my.astype(jnp.int32), idx_local)
        else:
            part = _bag_partial_scan(packed_local, idx_local,
                                     remap=slot_map, bank=bank_map,
                                     my_bank=my, off=off_local)
        return jax.lax.psum(part, bank)

    bank_map = t.remap_bank if bank_live is None \
        else _effective_bank_map(t.remap_bank, bank_live, t.n_banks)
    return jax.shard_map(
        fn, mesh=dist.mesh, check_vma=_check_vma(backend, interpret),
        in_specs=(P(bank, None), P(), P(), P(), idx_spec),
        out_specs=out_spec,
    )(t.packed, bank_map, t.remap_slot, off, idx)


def banked_gather(t: BankedTable, idx: Array, dist: DistCtx | None, *,
                  bank_live: Array | None = None) -> Array:
    """Dense per-position lookup (LM token embedding / BERT4Rec item seq)."""
    return banked_embedding_bag(t, idx, dist, reduce_bag=False,
                                bank_live=bank_live)


def _replica_failover_maps(t: ReplicatedTable,
                           bank_live: Array) -> tuple[Array, Array]:
    """(bank_flat, slot_flat) with dead copies rerouted to a live sibling.

    For every (row, column) whose bank is dead, substitute the row's FIRST
    live column — a surviving replica covers a dead bank's head reads
    instantly, with no replan and no kernel change. Rows with NO live copy
    keep a binary dead marker (1 vs my_bank = 0), resolving to the zero-row
    degraded substitute exactly like the single-copy ``_binary_live_map``
    path. Pure jnp on jit ARGUMENTS, so flipping a bank dead/alive never
    recompiles.
    """
    live_rc = bank_live[t.remap_bank]                  # (V, k) bool
    any_live = live_rc.any(axis=1)
    first_live = jnp.argmax(live_rc, axis=1)           # 0 when none live
    col = jnp.arange(t.k_max, dtype=jnp.int32)[None, :]
    eff = jnp.where(live_rc, col, first_live[:, None]).astype(jnp.int32)
    rows = jnp.arange(t.vocab)[:, None]
    eff_bank = t.remap_bank[rows, eff]
    eff_slot = t.remap_slot[rows, eff]
    bank_flat = jnp.where(any_live[:, None], 0, 1).astype(jnp.int32) \
        + jnp.zeros_like(eff)
    slot_flat = (eff_bank * t.rows_per_bank + eff_slot).astype(jnp.int32)
    return bank_flat.reshape(-1), slot_flat.reshape(-1)


@jax.named_scope("lookup")
def replicated_embedding_bag(t: ReplicatedTable, idx: Array,
                             dist: DistCtx | None, *, backend: str = "auto",
                             bwd_backend: str = "auto",
                             field_offsets: Array | None = None,
                             tile_b: int = 8, n_slots: int | None = None,
                             interpret: bool | None = None,
                             bank_live: Array | None = None,
                             with_traffic: bool = False):
    """Stages 1-3 over a REPLICATED table: idx (..., L) -> (..., dim) bag
    sums, with each bag reading copy ``wang_hash(bag) % k_max`` of every row
    it touches — a k-copy hot row's traffic splits k ways with no host-side
    routing. With ``k_max == 1`` (or no replicated rows) this is bit-exact
    to ``banked_embedding_bag``'s unsharded path on both backends.

    Differentiable: the backward scatters each bag's cotangent onto the
    copy its forward read came through, so summing a row's copies recovers
    the single-copy gradient exactly (fp32 accumulation on both backends).

    ``bank_live`` composes replication with fault tolerance: a dead copy's
    reads fail over to the row's first live copy instantly (zero extra
    latency, no replan); only rows with NO live copy degrade to the zero
    row (count them with ``degraded_row_counts`` on the (V, k) remap).

    The sharded (mesh) path is not wired yet — replication currently rides
    the unsharded serve loop; the multi-host mesh item in ROADMAP.md picks
    this up.

    ``with_traffic=True``: return becomes ``(out, BankTraffic)`` — measured
    reads routed to the SAME copy the kernel's wang-hash pick reads (and,
    under ``bank_live``, the same failover column the maps substitute).
    """
    if dist is not None:
        raise ValueError("replicated_embedding_bag is unsharded-only for "
                         "now — see the multi-host serving mesh item in "
                         "ROADMAP.md")
    if with_traffic:
        out = replicated_embedding_bag(
            t, idx, dist, backend=backend, bwd_backend=bwd_backend,
            field_offsets=field_offsets, tile_b=tile_b, n_slots=n_slots,
            interpret=interpret, bank_live=bank_live)
        from repro.obs.traffic import (replicated_bank_read_counts,
                                       traffic_from_reads)
        reads = replicated_bank_read_counts(
            t.remap_bank, _traffic_rows(idx, field_offsets), t.n_banks,
            k_max=t.k_max, bank_live=bank_live)
        row_nbytes = t.packed.shape[-1] * np.dtype(t.packed.dtype).itemsize
        return out, traffic_from_reads(reads, row_nbytes)
    if backend == "tuned":
        backend, tile_b, n_slots = _dispatch(
            "replicated", vocab=t.vocab, dim=t.dim,
            batch=int(np.prod(idx.shape[:-1])), bag_len=idx.shape[-1],
            n_fields=1 if field_offsets is None
            else int(np.shape(field_offsets)[0]),
            k_max=t.k_max, bwd_backend=bwd_backend,
            tile_b=tile_b, n_slots=n_slots)
    backend = _resolve_backend(backend)
    bwd = _resolve_bwd(bwd_backend, backend)
    interpret = _default_interpret(interpret)
    off = jnp.zeros((1,), jnp.int32) if field_offsets is None \
        else jnp.asarray(field_offsets, jnp.int32)
    if bank_live is None:
        bank_flat = t.flat_bank()
        slot_flat = t.flat_remap()
        my = jnp.full((), -1, jnp.int32)
    else:
        bank_flat, slot_flat = _replica_failover_maps(t, bank_live)
        my = jnp.zeros((), jnp.int32)
    cfg = (tile_b, interpret, backend, bwd, t.k_max, n_slots)
    return _replicated_bag(cfg, t.packed, bank_flat, slot_flat, off, my, idx)


@jax.named_scope("lookup")
def tiered_embedding_bag(fp_packed: Array, tt, idx: Array,
                         dist: DistCtx | None, *, backend: str = "auto",
                         bwd_backend: str = "auto",
                         field_offsets: Array | None = None,
                         tile_b: int = 8, n_slots: int = 2,
                         interpret: bool | None = None,
                         with_traffic: bool = False):
    """Stages 1-3 over a TIERED table (repro.quant.TieredTable): the fused
    lookup path with per-row dequant applied in-kernel (pallas) or in-scan
    (jnp) — idx (..., L) -> (..., dim) fp32 bag sums.

    ``fp_packed`` is the fp master table the payload was quantized from
    (same packed layout as ``tt``): the forward never reads its values, but
    gradients flow straight through onto it (``bwd_backend`` selects the
    scatter like the full-precision path). Serving can pass the live
    ``params['emb_packed']`` unchanged. One-hot fields fold in as length-1
    bags — the dense-gather semantics of ``banked_gather`` at fp32.
    """
    if with_traffic:
        out = tiered_embedding_bag(
            fp_packed, tt, idx, dist, backend=backend,
            bwd_backend=bwd_backend, field_offsets=field_offsets,
            tile_b=tile_b, n_slots=n_slots, interpret=interpret)
        from repro.obs.traffic import tiered_bank_traffic
        from repro.quant import tier_nbytes
        return out, tiered_bank_traffic(
            tt.remap_bank, tt.remap_slot, tt.rows_per_bank, tt.tier,
            tier_nbytes(tt.dim, tt.hot_dtype),
            _traffic_rows(idx, field_offsets), tt.n_banks)
    if backend == "tuned":
        backend, tile_b, n_slots = _dispatch(
            "tiered", vocab=int(tt.remap_bank.shape[0]), dim=tt.dim,
            batch=int(np.prod(idx.shape[:-1])), bag_len=idx.shape[-1],
            n_fields=1 if field_offsets is None
            else int(np.shape(field_offsets)[0]),
            tier_mix=tt.hot_dtype, bwd_backend=bwd_backend,
            tile_b=tile_b, n_slots=n_slots)
    backend = _resolve_backend(backend)
    bwd = _resolve_bwd(bwd_backend, backend)
    interpret = _default_interpret(interpret)
    if fp_packed.shape[0] != tt.payload.shape[0]:
        raise ValueError(
            f"fp table rows {fp_packed.shape[0]} != tiered payload rows "
            f"{tt.payload.shape[0]}: the straight-through gradient needs "
            f"the layout the payload was quantized from")
    off = jnp.zeros((1,), jnp.int32) if field_offsets is None \
        else jnp.asarray(field_offsets, jnp.int32)
    scale_bits = jax.lax.bitcast_convert_type(tt.scale, jnp.int32)
    cfg = (tile_b, interpret, backend, bwd, tt.dim, tt.hot_dtype, n_slots)

    if dist is None:
        return _tiered_bag(cfg, fp_packed, tt.payload, scale_bits, tt.tier,
                           tt.remap_bank, tt.flat_remap(), off,
                           jnp.full((), -1, jnp.int32), idx)

    P = jax.sharding.PartitionSpec
    dp_ok = idx.shape[0] % dist.dp_size() == 0
    dp = (dist.dp_axes if len(dist.dp_axes) > 1 else dist.dp_axes[0]) \
        if dp_ok else None
    bank_ax = dist.bank_axis
    idx_spec = P(dp, *([None] * (idx.ndim - 1)))
    out_spec = P(dp, *([None] * (idx.ndim - 1)))

    def fn(fp_local, pay_local, sc_local, tier_local, bank_map, slot_map,
           off_local, idx_local):
        my = jax.lax.axis_index(bank_ax)
        part = _tiered_bag(cfg, fp_local, pay_local, sc_local, tier_local,
                           bank_map, slot_map, off_local,
                           my.astype(jnp.int32), idx_local)
        return jax.lax.psum(part, bank_ax)

    return jax.shard_map(
        fn, mesh=dist.mesh, check_vma=_check_vma(backend, interpret),
        in_specs=(P(bank_ax, None), P(bank_ax, None), P(bank_ax),
                  P(bank_ax), P(), P(), P(), idx_spec),
        out_specs=out_spec,
    )(fp_packed, tt.payload, scale_bits, tt.tier, tt.remap_bank,
      tt.remap_slot, off, idx)


@jax.named_scope("lookup")
def banked_cache_residual_bag(t: BankedTable, cache: BankedTable,
                              cache_idx: Array, residual_idx: Array,
                              dist: DistCtx | None, *, backend: str = "auto",
                              bwd_backend: str = "auto", tile_b: int = 8,
                              n_slots: int = 2,
                              interpret: bool | None = None,
                              bank_live: Array | None = None,
                              with_traffic: bool = False):
    """Cache-aware fused lookup (paper Fig. 7): one stage-2 pass computes
    ``Σ cache_partials + Σ residual_rows`` per bag.

    cache_idx (..., Lc) ids into the partial-sum cache table; residual_idx
    (..., Lr) union-vocab rows into the EMT. Both tables are banked over the
    same axis; the combined partial takes ONE psum (half the stage-3 traffic
    of two separate lookups). ``bwd_backend='pallas'`` routes the dual
    gradient scatter (EMT + cache table) through the sorted-run kernel.

    ``bank_live`` masks BOTH tables: a dead bank loses its EMT rows and its
    cache entries alike (they share the physical bank), each resolving to the
    zero-row degraded substitute. Same zero-recompile argument contract as
    ``banked_embedding_bag``.
    """
    if with_traffic:
        out = banked_cache_residual_bag(
            t, cache, cache_idx, residual_idx, dist, backend=backend,
            bwd_backend=bwd_backend, tile_b=tile_b, n_slots=n_slots,
            interpret=interpret, bank_live=bank_live)
        from repro.obs.traffic import (cached_bank_read_counts,
                                       traffic_from_reads)
        reads = cached_bank_read_counts(
            cache.remap_bank, cache_idx, t.remap_bank, residual_idx,
            t.n_banks, bank_live=bank_live)
        row_nbytes = t.packed.shape[-1] * np.dtype(t.packed.dtype).itemsize
        return out, traffic_from_reads(reads, row_nbytes)
    if backend == "tuned":
        backend, tile_b, n_slots = _dispatch(
            "fused", vocab=t.vocab, dim=t.dim,
            batch=int(np.prod(cache_idx.shape[:-1])),
            bag_len=f"{cache_idx.shape[-1]}+{residual_idx.shape[-1]}",
            bwd_backend=bwd_backend, tile_b=tile_b, n_slots=n_slots)
    backend = _resolve_backend(backend)
    bwd = _resolve_bwd(bwd_backend, backend)
    interpret = _default_interpret(interpret)

    if dist is None:
        if bank_live is None:
            e_bank, c_bank = t.remap_bank, cache.remap_bank
            my = jnp.full((), -1, jnp.int32)
        else:
            e_bank = _binary_live_map(t.remap_bank, bank_live)
            c_bank = _binary_live_map(cache.remap_bank, bank_live)
            my = jnp.zeros((), jnp.int32)
        if backend == "pallas":
            return _pallas_cache_bag(
                (tile_b, interpret, bwd, n_slots), t.packed, cache.packed,
                e_bank, t.flat_remap(), c_bank,
                cache.flat_remap(), my, cache_idx, residual_idx)
        zero = jnp.zeros((1,), jnp.int32)
        scan_bank = None if bank_live is None else e_bank
        scan_cbank = None if bank_live is None else c_bank
        scan_my = None if bank_live is None else my
        part = _bag_partial_scan(t.packed, residual_idx,
                                 remap=t.flat_remap(), bank=scan_bank,
                                 my_bank=scan_my, off=zero)
        return part + _bag_partial_scan(
            cache.packed, cache_idx, remap=cache.flat_remap(),
            bank=scan_cbank, my_bank=scan_my, off=zero).astype(part.dtype)

    P = jax.sharding.PartitionSpec
    dp_ok = cache_idx.shape[0] % dist.dp_size() == 0
    dp = (dist.dp_axes if len(dist.dp_axes) > 1 else dist.dp_axes[0]) \
        if dp_ok else None
    bank = dist.bank_axis
    ci_spec = P(dp, *([None] * (cache_idx.ndim - 1)))
    ri_spec = P(dp, *([None] * (residual_idx.ndim - 1)))
    out_spec = P(dp, *([None] * (cache_idx.ndim - 1)))

    def fn(emt_local, cache_local, e_bank, e_slot, c_bank, c_slot,
           ci_local, ri_local):
        my = jax.lax.axis_index(bank)
        if backend == "pallas":
            part = _pallas_cache_bag(
                (tile_b, interpret, bwd, n_slots), emt_local, cache_local,
                e_bank, e_slot,
                c_bank, c_slot, my.astype(jnp.int32), ci_local, ri_local)
        else:
            zero = jnp.zeros((1,), jnp.int32)
            part = _bag_partial_scan(emt_local, ri_local, remap=e_slot,
                                     bank=e_bank, my_bank=my, off=zero)
            part = part + _bag_partial_scan(
                cache_local, ci_local, remap=c_slot, bank=c_bank, my_bank=my,
                off=zero).astype(part.dtype)
        return jax.lax.psum(part, bank)

    if bank_live is None:
        e_map, c_map = t.remap_bank, cache.remap_bank
    else:
        e_map = _effective_bank_map(t.remap_bank, bank_live, t.n_banks)
        c_map = _effective_bank_map(cache.remap_bank, bank_live, cache.n_banks)
    return jax.shard_map(
        fn, mesh=dist.mesh, check_vma=_check_vma(backend, interpret),
        in_specs=(P(bank, None), P(bank, None), P(), P(), P(), P(),
                  ci_spec, ri_spec),
        out_specs=out_spec,
    )(t.packed, cache.packed, e_map, t.remap_slot,
      c_map, cache.remap_slot, cache_idx, residual_idx)


# ---------------------------------------------------------------------------
# CSR-ragged lookup
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pallas_csr_bag(cfg: tuple, packed: Array, bank: Array, slot: Array,
                    my: Array, indices: Array, seg: Array,
                    offs_ext: Array) -> Array:
    """cfg = (tile_b, interpret, num_bags_padded, bwd, n_slots)."""
    from repro.kernels.embedding_bag import csr_bag_pallas
    tile_b, interpret, nb_pad, _, n_slots = cfg
    table, d = _pad_lanes(packed, interpret)
    out = csr_bag_pallas(table, bank, slot, my.reshape(1).astype(jnp.int32),
                         indices.astype(jnp.int32), seg.astype(jnp.int32),
                         offs_ext.astype(jnp.int32), nb_pad,
                         tile_b=tile_b, interpret=interpret,
                         n_slots=n_slots)
    return out[:, :d]


def _pallas_csr_bag_fwd(cfg, packed, bank, slot, my, indices, seg, offs_ext):
    return _pallas_csr_bag(cfg, packed, bank, slot, my, indices, seg,
                           offs_ext), (packed, bank, slot, my, indices, seg)


def _pallas_csr_bag_bwd(cfg, res, ct):
    tile_b, interpret, nb_pad, bwd, n_slots = cfg
    packed, bank, slot, my, indices, seg = res
    if bwd == "pallas":
        from repro.kernels.embedding_bag import ct_scatter_csr_pallas
        d_tab = ct_scatter_csr_pallas(
            ct, indices, seg, bank, slot, my.reshape(1).astype(jnp.int32),
            packed.shape[0], packed.dtype, tile_s=tile_b,
            interpret=interpret, n_slots=n_slots)
        return (d_tab, None, None, None, None, None, None)
    valid = indices >= 0
    row = jnp.where(valid, indices, 0)
    mine = valid & ((my < 0) | (bank[row] == my))
    src = jnp.where(mine, slot[row], 0)
    upd = jnp.where(mine[:, None], ct[seg], 0).astype(jnp.float32)
    d_tab = jnp.zeros(packed.shape, jnp.float32).at[src].add(upd)
    return (d_tab.astype(packed.dtype), None, None, None, None, None, None)


_pallas_csr_bag.defvjp(_pallas_csr_bag_fwd, _pallas_csr_bag_bwd)


def csr_embedding_bag(t: BankedTable, indices: Array, offsets: Array,
                      num_bags: int, dist: DistCtx | None, *,
                      backend: str = "auto", bwd_backend: str = "auto",
                      tile_b: int = 8, n_slots: int = 2,
                      interpret: bool | None = None,
                      with_traffic: bool = False):
    """CSR-ragged variant (indices flat + offsets), bag-summed.

    Ragged bags cannot shard on batch without equal per-shard totals, so the
    flat stream is replicated across dp as well — used for the paper-faithful
    serving path at modest batch (the paper's batch is 64); the rectangular
    ``banked_embedding_bag`` is the scale path.

    The pallas backend walks each tile's contiguous CSR range with the same
    double-buffered row DMA as the rectangular kernel (bag id = prefetched
    segment id), so ragged bags fuse without padding to a rectangle.
    """
    if with_traffic:
        out = csr_embedding_bag(
            t, indices, offsets, num_bags, dist, backend=backend,
            bwd_backend=bwd_backend, tile_b=tile_b, n_slots=n_slots,
            interpret=interpret)
        from repro.obs.traffic import bank_read_counts, traffic_from_reads
        reads = bank_read_counts(t.remap_bank, indices, t.n_banks)
        row_nbytes = t.packed.shape[-1] * np.dtype(t.packed.dtype).itemsize
        return out, traffic_from_reads(reads, row_nbytes)
    if backend == "tuned":
        backend, tile_b, n_slots = _dispatch(
            "csr", vocab=t.vocab, dim=t.dim, batch=int(num_bags),
            bag_len="ragged", bwd_backend=bwd_backend,
            tile_b=tile_b, n_slots=n_slots)
    backend = _resolve_backend(backend)
    bwd = _resolve_bwd(bwd_backend, backend)
    interpret = _default_interpret(interpret)
    from repro.sparse.ops import offsets_to_segment_ids
    total = indices.shape[0]
    seg = offsets_to_segment_ids(offsets, total)
    nb_pad = -(-num_bags // tile_b) * tile_b
    offs_ext = jnp.concatenate(
        [offsets.astype(jnp.int32),
         jnp.full((nb_pad + 1 - num_bags,), total, jnp.int32)])

    if dist is None:
        if backend == "pallas":
            out = _pallas_csr_bag((tile_b, interpret, nb_pad, bwd, n_slots),
                                  t.packed,
                                  t.remap_bank, t.flat_remap(),
                                  jnp.full((), -1, jnp.int32), indices, seg,
                                  offs_ext)
            return out[:num_bags]
        rows = lookup_unsharded(t, indices[:, None], reduce_bag=True)
        return jax.ops.segment_sum(rows, seg, num_bags)

    P = jax.sharding.PartitionSpec

    def fn(packed_local, bank_map, slot_map, idx_local, seg_local, offs_local):
        my = jax.lax.axis_index(dist.bank_axis)
        if backend == "pallas":
            part = _pallas_csr_bag((tile_b, interpret, nb_pad, bwd, n_slots),
                                   packed_local, bank_map, slot_map,
                                   my.astype(jnp.int32), idx_local,
                                   seg_local, offs_local)[:num_bags]
        else:
            part = _local_gather_partial(packed_local, bank_map, slot_map,
                                         idx_local, my)
            part = jax.ops.segment_sum(part, seg_local, num_bags)
        return jax.lax.psum(part, dist.bank_axis)

    return jax.shard_map(
        fn, mesh=dist.mesh, check_vma=_check_vma(backend, interpret),
        in_specs=(P(dist.bank_axis, None), P(), P(), P(), P(), P()),
        out_specs=P(),
    )(t.packed, t.remap_bank, t.remap_slot, indices, seg, offs_ext)


# ---------------------------------------------------------------------------
# CSR batch sharding: balanced split of the ragged stream over dp shards
# ---------------------------------------------------------------------------

def balanced_csr_shards(offsets: np.ndarray, n_shards: int) -> np.ndarray:
    """(n_shards + 1,) bag-aligned cut points with near-equal per-shard INDEX
    totals (not bag counts — ragged bags make those very different).

    Cut k lands on the bag boundary closest to total * k / n_shards; with
    any bag smaller than total / n_shards the per-shard imbalance is at most
    one bag's length.
    """
    offsets = np.asarray(offsets, np.int64)
    num_bags = offsets.shape[0] - 1
    total = int(offsets[-1])
    targets = total * np.arange(1, n_shards) / n_shards
    cuts = np.searchsorted(offsets, targets, side="left")
    # snap to the nearer of the two surrounding boundaries
    left = np.clip(cuts - 1, 0, num_bags)
    cuts = np.where(targets - offsets[left] < offsets[np.clip(cuts, 0,
                                                              num_bags)]
                    - targets, left, cuts)
    cuts = np.clip(cuts, 0, num_bags)
    bounds = np.concatenate([[0], np.maximum.accumulate(cuts), [num_bags]])
    return bounds.astype(np.int64)


def shard_csr_batch(indices: np.ndarray, offsets: np.ndarray,
                    n_shards: int) -> dict:
    """Host-side prep (pre-processing stage, like ``rewrite_bags``): split a
    CSR batch into ``n_shards`` equal-total slices, padded to one static
    shape. Returns stacked per-shard arrays ready for
    ``csr_embedding_bag_sharded``:

      idx (S, cap)   flat row ids, -1 padded
      seg (S, cap)   GLOBAL bag id per entry (num_bags on padding)
      bounds (S+1,)  the bag cut points
    """
    indices = np.asarray(indices)
    offsets = np.asarray(offsets, np.int64)
    num_bags = offsets.shape[0] - 1
    seg = np.repeat(np.arange(num_bags), np.diff(offsets))
    bounds = balanced_csr_shards(offsets, n_shards)
    caps = offsets[bounds[1:]] - offsets[bounds[:-1]]
    cap = max(int(caps.max()), 1)
    idx_s = np.full((n_shards, cap), -1, dtype=np.int32)
    seg_s = np.full((n_shards, cap), num_bags, dtype=np.int32)
    for s in range(n_shards):
        lo, hi = int(offsets[bounds[s]]), int(offsets[bounds[s + 1]])
        idx_s[s, :hi - lo] = indices[lo:hi]
        seg_s[s, :hi - lo] = seg[lo:hi]
    return {"idx": idx_s, "seg": seg_s, "bounds": bounds}


def csr_embedding_bag_sharded(t: BankedTable, indices: np.ndarray,
                              offsets: np.ndarray, num_bags: int,
                              dist: DistCtx | None, *, backend: str = "auto",
                              bwd_backend: str = "auto", tile_b: int = 8,
                              n_slots: int = 2,
                              interpret: bool | None = None) -> Array:
    """CSR bag sums with the flat stream SHARDED over dp (vs the replicating
    ``csr_embedding_bag``): each dp shard owns a contiguous bag range chosen
    by ``balanced_csr_shards`` so per-shard index totals are near-equal, does
    its own stage 2 against its bank slice, and the (num_bags, D) partials
    combine in one psum over (dp, bank).

    ``indices``/``offsets`` must be HOST (concrete) arrays — the balanced
    split is data-dependent and runs in the pre-processing stage. ``offsets``
    may be starts-only (length num_bags, ``csr_embedding_bag``'s convention)
    or include the trailing total (length num_bags + 1).
    """
    indices = np.asarray(indices)
    offsets = np.asarray(offsets, np.int64)
    if offsets.shape[0] == num_bags:       # starts-only -> append the total
        offsets = np.concatenate([offsets, [indices.shape[0]]])
    assert offsets.shape[0] == num_bags + 1, (offsets.shape, num_bags)
    if dist is None or dist.dp_size() == 1:
        return csr_embedding_bag(t, jnp.asarray(indices),
                                 jnp.asarray(offsets[:num_bags]), num_bags,
                                 dist, backend=backend,
                                 bwd_backend=bwd_backend, tile_b=tile_b,
                                 n_slots=n_slots, interpret=interpret)
    if backend == "tuned":
        backend, tile_b, n_slots = _dispatch(
            "csr", vocab=t.vocab, dim=t.dim, batch=int(num_bags),
            bag_len="ragged", bwd_backend=bwd_backend,
            tile_b=tile_b, n_slots=n_slots)
    backend = _resolve_backend(backend)
    bwd = _resolve_bwd(bwd_backend, backend)
    interpret = _default_interpret(interpret)
    nd = dist.dp_size()
    sh = shard_csr_batch(indices, offsets, nd)
    nb_pad = -(-num_bags // tile_b) * tile_b
    bounds = sh["bounds"]
    # per-shard clipped cumulative offsets: bags outside the shard's range
    # collapse to empty [x, x) spans, so the CSR kernel's per-tile walk
    # touches only owned entries
    offs_ext = np.concatenate([offsets, np.full(nb_pad + 1 - num_bags - 1,
                                                offsets[-1])])
    lo = offsets[bounds[:-1]][:, None]                     # (S, 1)
    hi = offsets[bounds[1:]][:, None]
    offs_s = np.clip(offs_ext[None, :] - lo, 0, hi - lo).astype(np.int32)

    P = jax.sharding.PartitionSpec
    dp = dist.dp_axes if len(dist.dp_axes) > 1 else dist.dp_axes[0]
    bank = dist.bank_axis

    def fn(packed_local, bank_map, slot_map, idx_s, seg_s, offs_local):
        my = jax.lax.axis_index(bank)
        idx_local = idx_s[0]
        seg_local = seg_s[0]
        if backend == "pallas":
            part = _pallas_csr_bag((tile_b, interpret, nb_pad, bwd, n_slots),
                                   packed_local, bank_map, slot_map,
                                   my.astype(jnp.int32), idx_local,
                                   seg_local, offs_local[0])[:num_bags]
        else:
            part = _local_gather_partial(packed_local, bank_map, slot_map,
                                         idx_local, my)
            part = jax.ops.segment_sum(part, seg_local, num_bags)
        return jax.lax.psum(part, (*dist.dp_axes, bank))

    return jax.shard_map(
        fn, mesh=dist.mesh, check_vma=_check_vma(backend, interpret),
        in_specs=(P(bank, None), P(), P(), P(dp, None), P(dp, None),
                  P(dp, None)),
        out_specs=P(),
    )(t.packed, t.remap_bank, t.remap_slot, jnp.asarray(sh["idx"]),
      jnp.asarray(sh["seg"]), jnp.asarray(offs_s))


# ---------------------------------------------------------------------------
# column-split table (the paper's N_c axis, TPU rendition)
# ---------------------------------------------------------------------------

def col_split_embedding_bag(table: Array, idx: Array, dist: DistCtx | None,
                            *, reduce_bag: bool = True) -> Array:
    """Uniform column split: table (vocab, dim) sharded P(None, bank_axis).

    Every bank gathers ALL bag indices for its dim slice; no mask, no psum —
    stage 3 is an implicit all-gather when the consumer needs the full dim.
    Expressed via GSPMD sharding constraint so XLA schedules the collective.
    """
    valid = idx >= 0
    rows = jnp.take(table, jnp.where(valid, idx, 0), axis=0)
    rows = jnp.where(valid[..., None], rows, 0)
    out = rows.sum(axis=-2) if reduce_bag else rows
    if dist is not None:
        P = jax.sharding.PartitionSpec
        dp = dist.dp_axes if len(dist.dp_axes) > 1 else dist.dp_axes[0]
        spec = P(dp, *([None] * (out.ndim - 2)), dist.bank_axis)
        out = jax.lax.with_sharding_constraint(
            out, jax.sharding.NamedSharding(dist.mesh, spec))
    return out
