"""Live migration: apply a new PartitionPlan to an already-sharded table.

Rebuilding a table from scratch on every replan would stream the full vocab
through host memory and drop the serving loop for seconds; migration reuses
what is already resident:

  * rows whose bank does NOT change are a per-bank permutation gather
    (slot reshuffle inside the bank's own HBM block — no traffic on the wire),
  * rows that change bank ride ONE psum over the bank axis (`repro.dist`
    rendition of a cross-bank row exchange) — COMPACTED to the moved set:
    each bank gathers the rows it is giving up into an (n_moved, D) buffer
    at their host-assigned position in the global moved list, and the
    reduction materializes exactly the moved rows (an incremental replan
    moves a few percent of the vocab, so the wire cost tracks the drift
    instead of the full packed size; ``exchange='full'`` keeps the original
    packed-size buffer as the parity baseline),

and the swap to the new (packed, remap_bank, remap_slot) triple happens
between micro-batches on the host — the jitted serve step never observes a
half-migrated table. Keeping ``rows_per_bank`` at a fixed capacity across
plans keeps every array shape static, so the swap does not trigger a
recompile (runtime.py relies on this).

``migrate_table`` is exact: the result is bit-identical to ``pack_table`` of
the same row values under the new plan (tests/test_workload.py asserts it,
per-bank, on both the single-device and shard_map paths).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.embedding import BankedTable, DistCtx
from repro.core.partitioning import PartitionPlan

Array = jax.Array


def _flat_positions(plan: PartitionPlan, rows_per_bank: int) -> np.ndarray:
    return (plan.bank_of_row.astype(np.int64) * rows_per_bank
            + plan.slot_of_row).astype(np.int32)


def resolve_rows_per_bank(plan: PartitionPlan,
                          rows_per_bank: int | None) -> int:
    rpb = int(plan.max_rows_per_bank if rows_per_bank is None
              else rows_per_bank)
    if rpb < plan.max_rows_per_bank:
        raise ValueError(f"rows_per_bank {rpb} < plan max "
                         f"{plan.max_rows_per_bank}")
    return rpb


def permute_packed_rows(arr: Array, old_flat: np.ndarray,
                        new_flat: np.ndarray, new_len: int) -> Array:
    """Reindex the leading (packed-row) dim of ``arr`` from the old flat
    layout to the new one; unpopulated pad rows become zeros (pack_table
    semantics). Works for (R, D) tables and (R,) row-wise optimizer state."""
    out = jnp.zeros((new_len,) + arr.shape[1:], arr.dtype)
    return out.at[jnp.asarray(new_flat)].set(
        jnp.take(arr, jnp.asarray(old_flat), axis=0))


def migrate_table(t: BankedTable, new_plan: PartitionPlan,
                  dist: DistCtx | None = None, *,
                  rows_per_bank: int | None = None,
                  exchange: str = "compact") -> BankedTable:
    """Re-layout ``t`` under ``new_plan`` without re-initializing.

    ``rows_per_bank`` pins the per-bank capacity (pass the table's current
    value to keep shapes — and therefore compiled executables — stable).
    ``exchange`` picks the sharded moved-row path: 'compact' psums only the
    gathered (n_moved, D) buffer, 'full' the original packed-size buffer
    (bit-identical results; tests assert it).
    """
    if new_plan.vocab != t.vocab:
        raise ValueError(f"plan vocab {new_plan.vocab} != table {t.vocab}")
    if exchange not in ("compact", "full"):
        raise ValueError(f"exchange must be 'compact' or 'full', "
                         f"got {exchange!r}")
    new_rpb = resolve_rows_per_bank(new_plan, rows_per_bank)
    old_flat = np.asarray(
        (np.asarray(t.remap_bank, np.int64) * t.rows_per_bank
         + np.asarray(t.remap_slot)), np.int32)
    new_flat = _flat_positions(new_plan, new_rpb)

    if dist is None:
        packed = permute_packed_rows(
            t.packed, old_flat, new_flat, new_plan.n_banks * new_rpb)
    else:
        packed = _migrate_packed_sharded(t, new_plan, new_rpb, dist,
                                         exchange=exchange)

    return BankedTable(
        packed=packed,
        remap_bank=jnp.asarray(new_plan.bank_of_row, jnp.int32),
        remap_slot=jnp.asarray(new_plan.slot_of_row, jnp.int32),
        n_banks=new_plan.n_banks,
        rows_per_bank=new_rpb,
    )


def _migrate_packed_sharded(t: BankedTable, new_plan: PartitionPlan,
                            new_rpb: int, dist: DistCtx, *,
                            exchange: str = "compact") -> Array:
    """shard_map migration: local permutation for stay rows, psum exchange
    for moved rows. Requires the bank count to match the mesh's bank axis
    (as banked_embedding_bag does).

    The moved-row exchange has two shapes: 'compact' (default) enumerates
    the moved set HOST-side (the remaps are concrete between micro-batches —
    the same pre-processing contract as ``shard_csr_batch``) and psums an
    (n_moved, D) buffer where each moved row owns one host-assigned
    position; 'full' scatters into an (n_banks * new_rpb, D) buffer at the
    rows' new flat positions (the original path, kept as parity baseline).
    Both are exact: every buffer position is written by exactly one bank.
    """
    if new_plan.n_banks != t.n_banks:
        raise ValueError("sharded migration keeps the bank count (the mesh "
                         f"axis is fixed): {t.n_banks} -> {new_plan.n_banks}")
    P = jax.sharding.PartitionSpec
    bank = dist.bank_axis
    n_banks = t.n_banks
    D = t.dim
    dtype = t.packed.dtype
    old_bank_h = np.asarray(t.remap_bank, np.int32)
    new_bank_h = np.asarray(new_plan.bank_of_row, np.int32)
    new_bank = jnp.asarray(new_bank_h)
    new_slot = jnp.asarray(new_plan.slot_of_row, jnp.int32)

    def stay_rows(old_local, ob, osl, nb, ns, my):
        mine_old = ob == my
        vals = jnp.take(old_local, jnp.where(mine_old, osl, 0), axis=0)
        vals = jnp.where(mine_old[:, None], vals, jnp.zeros((), dtype))
        stay = mine_old & (nb == my)
        local = jnp.zeros((new_rpb, D), dtype)
        return mine_old, vals, local.at[jnp.where(stay, ns, new_rpb)].set(
            jnp.where(stay[:, None], vals, jnp.zeros((), dtype)),
            mode="drop")

    if exchange == "compact":
        moved_rows = np.nonzero(old_bank_h != new_bank_h)[0]
        if moved_rows.size == 0:
            # pure in-bank permutation: no collective at all
            def fn_local(old_local, ob, osl, nb, ns):
                my = jax.lax.axis_index(bank)
                return stay_rows(old_local, ob, osl, nb, ns, my)[2]

            return jax.shard_map(
                fn_local, mesh=dist.mesh,
                in_specs=(P(bank, None), P(), P(), P(), P()),
                out_specs=P(bank, None),
            )(t.packed, t.remap_bank, t.remap_slot, new_bank, new_slot)

        m_ob = jnp.asarray(old_bank_h[moved_rows])
        m_os = jnp.asarray(np.asarray(t.remap_slot, np.int32)[moved_rows])
        m_nb = jnp.asarray(new_bank_h[moved_rows])
        m_ns = jnp.asarray(new_plan.slot_of_row.astype(np.int32)[moved_rows])

        def fn(old_local, ob, osl, nb, ns, mob, mos, mnb, mns):
            my = jax.lax.axis_index(bank)
            _, _, local = stay_rows(old_local, ob, osl, nb, ns, my)
            # each bank fills ITS outgoing rows at their global moved-list
            # position; the psum materializes the full moved set (n_moved, D)
            out_mine = mob == my
            buf = jnp.take(old_local, jnp.where(out_mine, mos, 0), axis=0)
            buf = jnp.where(out_mine[:, None], buf, jnp.zeros((), dtype))
            buf = jax.lax.psum(buf, bank)
            in_mine = mnb == my
            return local.at[jnp.where(in_mine, mns, new_rpb)].set(
                jnp.where(in_mine[:, None], buf, jnp.zeros((), dtype)),
                mode="drop")

        return jax.shard_map(
            fn, mesh=dist.mesh,
            in_specs=(P(bank, None), P(), P(), P(), P(), P(), P(), P(), P()),
            out_specs=P(bank, None),
        )(t.packed, t.remap_bank, t.remap_slot, new_bank, new_slot,
          m_ob, m_os, m_nb, m_ns)

    def fn_full(old_local, ob, osl, nb, ns):
        my = jax.lax.axis_index(bank)
        mine_old, vals, local = stay_rows(old_local, ob, osl, nb, ns, my)
        # moved rows: scatter into the global layout, exchange via psum
        moved = mine_old & (nb != my)
        flat = jnp.where(moved, nb * new_rpb + ns, n_banks * new_rpb)
        buf = jnp.zeros((n_banks * new_rpb, D), dtype)
        buf = buf.at[flat].set(
            jnp.where(moved[:, None], vals, jnp.zeros((), dtype)),
            mode="drop")
        buf = jax.lax.psum(buf, bank)
        incoming = jax.lax.dynamic_slice(
            buf, (my * new_rpb, 0), (new_rpb, D))
        return local + incoming

    return jax.shard_map(
        fn_full, mesh=dist.mesh,
        in_specs=(P(bank, None), P(), P(), P(), P()),
        out_specs=P(bank, None),
    )(t.packed, t.remap_bank, t.remap_slot, new_bank, new_slot)


def migrate_replicated(base: BankedTable, rplan, *,
                       rows_per_bank: int | None = None):
    """Build the replicated side table for ``rplan`` from a live base table
    — the replica-lane swap's device path.

    Gathers the vocab rows once through the base remap (no host round-trip)
    and scatters every copy the plan calls for; bit-identical to
    ``pack_replicated`` of the unpacked rows (tests assert it), so a
    replica-count change swaps in a table indistinguishable from a fresh
    pack. ``rows_per_bank`` pins the shape across swaps like the other
    lanes.
    """
    from repro.core.embedding import ReplicatedTable
    rpb = int(rplan.max_rows_per_bank if rows_per_bank is None
              else rows_per_bank)
    if rpb < rplan.max_rows_per_bank:
        raise ValueError(f"rows_per_bank {rpb} < replica plan max "
                         f"{rplan.max_rows_per_bank}")
    if rplan.vocab != base.vocab:
        raise ValueError(f"replica plan vocab {rplan.vocab} != table "
                         f"{base.vocab}")
    rows = jnp.take(base.packed, base.flat_remap(), axis=0)     # (V, D)
    vv, rr = np.nonzero(np.arange(rplan.k_max)[None, :]
                        < rplan.copies[:, None])
    pos = (rplan.bank_of_copy[vv, rr].astype(np.int64) * rpb
           + rplan.slot_of_copy[vv, rr]).astype(np.int32)
    packed = jnp.zeros((rplan.n_banks * rpb, base.dim), base.packed.dtype)
    packed = packed.at[jnp.asarray(pos)].set(rows[jnp.asarray(vv)])
    return ReplicatedTable(
        packed=packed,
        remap_bank=jnp.asarray(rplan.bank_of_copy, jnp.int32),
        remap_slot=jnp.asarray(rplan.slot_of_copy, jnp.int32),
        n_banks=rplan.n_banks,
        rows_per_bank=rpb,
        k_max=rplan.k_max,
    )


def migrate_packed_leaves(tree, old_table: BankedTable,
                          new_plan: PartitionPlan, *,
                          rows_per_bank: int | None = None):
    """Migrate every packed-row-aligned leaf of a pytree — params AND
    optimizer state in one pass (train-loop replanning: the row-wise Adagrad
    accumulator must follow its row or hot rows restart cold).

    A leaf participates iff its leading dim equals the packed row count
    (``n_banks * rows_per_bank`` — vocab-scale, so dense layers never
    collide with it in practice).
    """
    plen = old_table.n_banks * old_table.rows_per_bank

    def f(x):
        if hasattr(x, "ndim") and x.ndim >= 1 and x.shape[0] == plen:
            return migrate_rowwise_state(x, old_table, new_plan,
                                         rows_per_bank=rows_per_bank)
        return x

    return jax.tree.map(f, tree)


def migrate_rowwise_state(arr: Array, old_table: BankedTable,
                          new_plan: PartitionPlan, *,
                          rows_per_bank: int | None = None) -> Array:
    """Migrate a packed-row-aligned auxiliary array (e.g. the row-wise
    Adagrad accumulator, shape (n_banks*rows_per_bank,) or (..., D)) with the
    same permutation as the table rows."""
    new_rpb = resolve_rows_per_bank(new_plan, rows_per_bank)
    old_flat = np.asarray(
        (np.asarray(old_table.remap_bank, np.int64) * old_table.rows_per_bank
         + np.asarray(old_table.remap_slot)), np.int32)
    new_flat = _flat_positions(new_plan, new_rpb)
    return permute_packed_rows(arr, old_flat, new_flat,
                               new_plan.n_banks * new_rpb)
