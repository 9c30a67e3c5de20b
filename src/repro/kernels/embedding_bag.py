"""Pallas TPU kernels: embedding-bag gather+reduce "near memory".

TPU adaptation of the paper's in-DPU lookup (DESIGN.md §5, paper §3.1/Fig. 7).
The table(s) stay in HBM (`pl.ANY`); the HBM row of every entry is known
from SMEM before vector memory is touched (the banked forward gets each
tile's resolved rows as an SMEM block, the other kernels scalar-prefetch
their indices and row->(bank, slot) remap vectors); rows stream HBM->VMEM
through an N-slot ring of `pltpu.make_async_copy` DMAs (`n_slots`: the
ring's depth, the pipeline depth the autotuner sweeps — `BAG_RING_DEPTH`
in the compiled forward ``updlrm_bag``, the two-slot ping-pong elsewhere).
Each grid step owns a tile of bags and writes only the reduced (tile_b, D)
block — the (B*L, D) gathered matrix a naive XLA gather would materialize
never exists.

Entry resolution:
  * per-field row offsets      — bag b belongs to field b % n_fields; its raw
    ids are shifted by `field_offsets[f]`, so ALL F sparse fields of a DLRM
    batch are one kernel invocation over (B*F, L) bags
  * bank/slot remap + ownership mask — the PIM stage-2 test `bank[row] == my`;
    foreign rows cost no DMA bandwidth to accumulate (they are masked)
  * fused cache + residual     — one accumulator walks the cache-entry stream
    then the residual stream (Fig. 7's `Σ cache_partials + Σ residual_rows`)

The banked forward resolves offsets, remap and ownership with XLA gathers
before the kernel (``resolve_entries``), so its SMEM is bounded by the tile;
the tiered, fused-cache and CSR kernels still resolve in-kernel from
scalar-prefetched remaps. Ownership is disabled by passing ``my_bank < 0``
(the unsharded path).

The TRAINING BACKWARD lives here too: ``ct_scatter_bag_pallas`` /
``ct_scatter_csr_pallas`` scatter-add the bag cotangents back onto the bank's
rows with the same double-buffered row DMA (cotangents in, accumulated rows
out) — slot collisions are resolved by a slot-sorted permutation computed in
the traced prep, never by atomics (see the backward section below).

Alignment: rows sit on the 128-lane boundary (the TPU analogue of the
paper's 8-byte MRAM alignment rule) — the plain/banked forward packs
``128 // D`` rows per lane row (``pack_lanes``), the other wrappers pad D;
each row copy is one (1, 128k) DMA — the ``N_c``-wide access of §3.1 with
TPU constants.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# N-slot row-DMA accumulate
# ---------------------------------------------------------------------------

#: Row-copy ring slots of the compiled ``updlrm_bag`` kernel. One row copy
#: is a few hundred ns of HBM latency, so the two-slot ping-pong waited on
#: every entry; a TPU v5e sweep at the serving and bulk-scoring shapes chose
#: this depth (PERF.md).
BAG_RING_DEPTH = 32
#: Copies ``updlrm_bag`` waits on at once, whose rows it then rotates and
#: adds back to back (``_ring_accumulate``).
BAG_GROUP = 8


def bag_ring_depth(n_slots: int | None, n_entries: int,
                   interpret: bool) -> int:
    """The ``updlrm_bag`` ring depth: an explicit ``n_slots`` wins; else
    the two-slot ping-pong in interpret mode (the CPU tests trace the depth
    they always have) and ``BAG_RING_DEPTH`` compiled, capped at a tile's
    ``n_entries`` so a small tile primes no copy it cannot use."""
    if n_slots is not None:
        return n_slots
    return 2 if interpret else min(BAG_RING_DEPTH, n_entries)


def _dma_accumulate(acc, table_ref, buf, sem, start, end, src_fn, meta_fn,
                    row_fn=None):
    """Accumulate table rows for entries [start, end) into per-bag sums.

    ``src_fn(e)``  -> local table row to fetch (already ownership-clamped)
    ``meta_fn(e)`` -> (bag_local, mine) — accumulator row and validity mask
    ``row_fn(e, raw)`` -> fp32 accumulator row from the DMA'd raw row
    (default: a plain fp32 cast; the tiered kernel dequantizes here).

    N-deep rotation over ``buf.shape[0]`` (1, D) VMEM slots: up to N row
    DMAs are in flight at once — the copy for entry e+N-1 is started before
    waiting on entry e, so N-1 HBM fetches overlap the VPU accumulate of the
    current row. The slot count is carried by the scratch SHAPE (see
    ``_scratch``), so the kernels need no extra parameter; N=2 is the
    classic ping-pong and traces the exact pre-N-slot graph. Slot reuse is
    hazard-free by construction: entry e+N-1's slot was last used by entry
    e-1, whose value was consumed (and semaphore waited) one iteration ago.
    """
    n_slots = buf.shape[0]

    def dma(e, slot):
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(src_fn(e), 1), :], buf.at[slot], sem.at[slot])

    for k in range(n_slots - 1):
        @pl.when(start + k < end)
        def _(k=k):
            dma(start + k, k).start()

    acc_rows = jax.lax.broadcasted_iota(jnp.int32, (acc.shape[0], 1), 0)

    def body(e, acc):
        slot = (e - start) % n_slots

        @pl.when(e + (n_slots - 1) < end)
        def _():
            dma(e + n_slots - 1, (slot + n_slots - 1) % n_slots).start()

        dma(e, slot).wait()
        bag_local, mine = meta_fn(e)
        raw = buf[slot]                                    # (1, D)
        val = raw.astype(jnp.float32) if row_fn is None else row_fn(e, raw)
        row = jnp.where(mine, val, 0.0)
        # masked add over the tile's rows: Mosaic cannot lower a dynamic-row
        # update of a loop-carried value; the other rows add +0.0 (exact)
        return acc + jnp.where(acc_rows == bag_local, row, 0.0)

    return jax.lax.fori_loop(start, end, body, acc)


def _ring_accumulate(acc, table_ref, buf, sem, n, src_fn, take_fn,
                     group: int):
    """Accumulate table rows for a tile's ``n`` entries (static) into
    per-bag sums, ``group`` entries at a time.

    ``src_fn(e)`` -> table row to fetch; ``take_fn(e, raw)`` -> (bag_local,
    row): the accumulator row, and the fp32 row to add from the copied raw
    row, zero where the entry is masked.

    The ``buf.shape[0]`` (1, W) VMEM slots form ``r = n_slots // group``
    slot groups; group g of entries lands in slot group ``g % r`` and
    signals its one semaphore, so ``r - 1`` groups of copies are in flight
    while one is consumed. Iteration g waits once for all of group g's
    copies, loads its rows, refills the slot group emptied one iteration
    earlier with group ``g + r - 1``, and then rotates and adds the rows:
    the rotations overlap each other and the refill's copy issue (on a
    v5e, refilling before the wait instead cost 9% more at depth 32). The
    last ``r - 1`` groups, with nothing left to refill, run in a loop of
    their own, so no branch splits that block. The rows are added in entry
    order, so the sums do not depend on the depth or the group.
    """
    r = buf.shape[0] // group
    assert r >= 2 and n % group == 0, (buf.shape[0], group, n)
    n_groups = n // group

    def start(g):
        s0 = (g % r) * group
        for k in range(group):
            pltpu.make_async_copy(
                table_ref.at[pl.ds(src_fn(g * group + k), 1), :],
                buf.at[s0 + k], sem.at[g % r]).start()

    for g in range(min(r - 1, n_groups)):
        start(g)

    acc_rows = jax.lax.broadcasted_iota(jnp.int32, (acc.shape[0], 1), 0)

    def body(g, acc, refill):
        s0 = (g % r) * group
        # a DMA semaphore counts bytes: a descriptor the size of the
        # group's slots waits for all of its copies at once
        pltpu.make_async_copy(buf.at[pl.ds(s0, group)],
                              buf.at[pl.ds(s0, group)], sem.at[g % r]).wait()
        raws = [buf[s0 + k] for k in range(group)]          # (1, W) each
        if refill:
            start(g + r - 1)
        for k, raw in enumerate(raws):
            bag_local, row = take_fn(g * group + k, raw)
            # masked add over the tile's rows: Mosaic cannot lower a
            # dynamic-row update of a loop-carried value; others add +0.0
            acc = acc + jnp.where(acc_rows == bag_local, row, 0.0)
        return acc

    split = max(n_groups - (r - 1), 0)
    acc = jax.lax.fori_loop(0, split, functools.partial(body, refill=True),
                            acc)
    return jax.lax.fori_loop(split, n_groups,
                             functools.partial(body, refill=False), acc)


def wang_hash(x: jax.Array) -> jax.Array:
    """Wang's 32-bit integer mix — the cheap deterministic in-kernel hash
    (a handful of shifts/xors/mults, no tables). Shared by the kernels and
    the jnp fallbacks so the replica pick ``wang_hash(bag) % k_max`` is
    bit-identical across backends."""
    x = x.astype(jnp.uint32)
    x = (x ^ jnp.uint32(61)) ^ (x >> 16)
    x = x * jnp.uint32(9)
    x = x ^ (x >> 4)
    x = x * jnp.uint32(0x27D4EB2D)
    x = x ^ (x >> 15)
    return x


def replica_of_bag(bag: jax.Array, k_max: int) -> jax.Array:
    """Replica column for a (global) bag id: hash so consecutive bags spread
    across copies, mod into [0, k_max)."""
    return (wang_hash(bag) % jnp.uint32(k_max)).astype(jnp.int32)


@jax.named_scope("resolve")
def resolve_entries(idx: jax.Array, bank: jax.Array, slot: jax.Array,
                    field_offsets: jax.Array, my_bank: jax.Array,
                    k_max: int = 1) -> jax.Array:
    """(NB, L) raw per-field ids -> (NB, L) int32 local table rows, -1 where
    the entry is padding or belongs to another bank.

    The forward kernel's and the backward scatter's shared prep, as XLA
    gathers outside the kernel: bag b belongs to field ``b % F`` and shifts
    its ids by ``field_offsets[b % F]``; the row resolves through the
    ``bank``/``slot`` remap, and ``my_bank < 0`` owns every row.

    ``k_max > 1`` is the replicated-table path: bank/slot are the FLATTENED
    ``(vocab * k_max,)`` replica-axis remap, and each bag reads copy
    ``wang_hash(bag) % k_max`` of every row it touches — replicas split a
    hot row's traffic with no host-side routing.
    """
    NB = idx.shape[0]
    bag = jnp.arange(NB, dtype=jnp.int32)[:, None]
    valid = idx >= 0
    row = jnp.where(valid, idx + field_offsets[bag % field_offsets.shape[0]],
                    0)
    if k_max > 1:
        row = row * k_max + replica_of_bag(bag, k_max)
    my = my_bank.reshape(())
    mine = valid & ((my < 0) | (bank[row] == my))
    return jnp.where(mine, slot[row], -1).astype(jnp.int32)


def _entry_fns(idx_ref, bank_ref, slot_ref, off_ref, my, b0, bag_len,
               n_fields):
    """(src_fn, meta_fn) for a rectangular (bags x bag_len) index stream with
    in-kernel field offsets, remap, and ownership mask (the tiered and
    fused-cache kernels, which still scalar-prefetch their remap). ``e`` is
    the tile-LOCAL entry id in [0, tile_b * bag_len).
    """
    def resolve(e):
        bag = b0 + e // bag_len
        raw = idx_ref[b0 * bag_len + e]
        valid = raw >= 0
        row = jnp.where(valid, raw + off_ref[bag % n_fields], 0)
        mine = valid & ((my < 0) | (bank_ref[row] == my))
        return row, mine

    def src_fn(e):
        row, mine = resolve(e)
        return jnp.where(mine, slot_ref[row], 0)

    def meta_fn(e):
        _, mine = resolve(e)
        return e // bag_len, mine

    return src_fn, meta_fn


def _plain_entry_fns(load, bag_len):
    """(src_fn, meta_fn) for an identity-mapped index stream — no remap
    vectors, no ownership test: ``load(e)`` is entry e's table row, -1
    skips."""
    def resolve(e):
        raw = load(e)
        return jnp.maximum(raw, 0), raw >= 0

    def src_fn(e):
        return resolve(e)[0]

    def meta_fn(e):
        return e // bag_len, resolve(e)[1]

    return src_fn, meta_fn


# ---------------------------------------------------------------------------
# padding helpers (shared by ops.py and core/embedding.py — ONE home for the
# 128-lane alignment rule and the -1 bag fill)
# ---------------------------------------------------------------------------

def effective_lengths(idx: jax.Array) -> jax.Array:
    """(B, L) -1-padded bags -> (B,) int32 count through the LAST valid
    entry (1 + its position; 0 for all-pad bags). Interior -1 holes are kept
    inside the walk — the in-kernel validity mask still skips them — so the
    early exit is exact for any padding pattern, suffix or not."""
    valid = idx >= 0
    last = idx.shape[1] - jnp.argmax(valid[:, ::-1], axis=1)
    return jnp.where(valid.any(axis=1), last, 0).astype(jnp.int32)


def pad_last_dim(x: jax.Array, mult: int = 128) -> tuple[jax.Array, int]:
    """Pad the trailing dim to a multiple (TPU lane alignment, §3.1 rule)."""
    d = x.shape[-1]
    pad = (-d) % mult
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, d


def pad_leading(x: jax.Array, mult: int, fill=-1) -> tuple[jax.Array, int]:
    """Pad the leading dim to a multiple with ``fill`` (-1 = padded bags)."""
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate(
            [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])
    return x, n


@jax.named_scope("relayout")
def pack_lanes(table: jax.Array) -> tuple[jax.Array, int]:
    """(V, D) -> (lane-dense table, rows per lane row ``pack``).

    A 2-D table's rows are padded to 128 lanes in HBM, so a (V, 32) fp32
    table occupies four times its bytes, and a (1, D) row DMA must be
    128-lane wide. Packing ``pack = 128 // D'`` rows into each 128-lane row
    (D' = D rounded up to a power of two) gives the kernel whole-lane rows
    at the table's own size; D > 128 pads to a multiple of 128 instead
    (``pack`` = 1). Row r lives in lane row ``r // pack`` at lane offset
    ``(r % pack) * D'``.
    """
    V, D = table.shape
    if D >= 128:
        return pad_last_dim(table)[0], 1
    width = 1 << (D - 1).bit_length()
    pack = 128 // width
    table = pad_leading(pad_last_dim(table, width)[0], pack, fill=0)[0]
    return table.reshape(-1, 128), pack


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _plain_bag_kernel(idx_ref, table_ref, out_ref, buf, sem, *,
                      tile_b: int, bag_len: int, pack: int, group: int):
    """``idx_ref`` is this tile's (tile_b, bag_len) SMEM block of table rows
    (-1 skips), so SMEM use scales with the tile, not the batch.
    ``table_ref`` is the ``pack_lanes`` layout: each entry DMAs its row's
    128-lane row, and a lane rotation brings the row to lanes [0, D'). The
    other lanes accumulate neighbours' values, which the wrapper drops."""
    width = table_ref.shape[1]

    def entry(e):
        return idx_ref[e // bag_len, e % bag_len]

    def src_fn(e):
        return jnp.maximum(entry(e), 0) // pack

    def take_fn(e, raw):
        ent = entry(e)                  # the entry's one SMEM read here
        val = raw.astype(jnp.float32)
        if pack > 1:
            shift = width - (jnp.maximum(ent, 0) % pack) * (width // pack)
            val = pltpu.roll(val, shift % width, 1)
        return e // bag_len, jnp.where(ent >= 0, val, 0.0)

    acc = jnp.zeros((tile_b, width), jnp.float32)
    acc = _ring_accumulate(acc, table_ref, buf, sem, tile_b * bag_len,
                           src_fn, take_fn, group)
    out_ref[...] = acc.astype(out_ref.dtype)


def _plain_fused_kernel(cache_idx_ref, resid_idx_ref, c_len_ref, r_len_ref,
                        cache_ref, emt_ref, out_ref, buf, sem, *,
                        tile_b: int, lc: int, lr: int, dim: int):
    b0 = pl.program_id(0) * tile_b
    acc = jnp.zeros((tile_b, dim), jnp.float32)
    c_src, c_meta = _plain_entry_fns(lambda e: cache_idx_ref[b0 * lc + e], lc)
    r_src, r_meta = _plain_entry_fns(lambda e: resid_idx_ref[b0 * lr + e], lr)
    # per-bag early exit on the prefetched effective lengths (CSR-style):
    # the walk stops at each bag's last valid entry instead of masked-
    # accumulating the full L — all-pad bags cost zero DMAs
    for i in range(tile_b):
        acc = _dma_accumulate(acc, cache_ref, buf, sem, i * lc,
                              i * lc + c_len_ref[b0 + i], c_src, c_meta)
        acc = _dma_accumulate(acc, emt_ref, buf, sem, i * lr,
                              i * lr + r_len_ref[b0 + i], r_src, r_meta)
    out_ref[...] = acc.astype(out_ref.dtype)


def _fused_cache_bag_kernel(cache_idx_ref, resid_idx_ref, c_len_ref,
                            r_len_ref, c_bank_ref, c_slot_ref, r_bank_ref,
                            r_slot_ref, my_ref, zero_off_ref, cache_ref,
                            emt_ref, out_ref, buf, sem, *, tile_b: int,
                            lc: int, lr: int, dim: int):
    """Fig. 7 fused lookup: Σ cache partial-sums + Σ residual EMT rows, one
    accumulator, one output write. Both streams run through the same
    ping-pong buffers; each bag's walk ends at its prefetched effective
    length (c_len/r_len — trailing -1 padding trimmed, CSR-style), so short
    bags in a long-L batch stop early instead of masked-accumulating L."""
    b0 = pl.program_id(0) * tile_b
    my = my_ref[0]
    acc = jnp.zeros((tile_b, dim), jnp.float32)
    c_src, c_meta = _entry_fns(cache_idx_ref, c_bank_ref, c_slot_ref,
                               zero_off_ref, my, b0, lc, 1)
    r_src, r_meta = _entry_fns(resid_idx_ref, r_bank_ref, r_slot_ref,
                               zero_off_ref, my, b0, lr, 1)
    for i in range(tile_b):
        acc = _dma_accumulate(acc, cache_ref, buf, sem, i * lc,
                              i * lc + c_len_ref[b0 + i], c_src, c_meta)
        acc = _dma_accumulate(acc, emt_ref, buf, sem, i * lr,
                              i * lr + r_len_ref[b0 + i], r_src, r_meta)
    out_ref[...] = acc.astype(out_ref.dtype)


def _tiered_bag_kernel(idx_ref, bank_ref, slot_ref, off_ref, my_ref,
                       tier_ref, scale_ref, payload_ref, out_ref, buf, sem, *,
                       tile_b: int, bag_len: int, n_fields: int, dim: int,
                       hot_dtype: str):
    """Banked bag sums over a TIERED byte payload, dequant in-kernel.

    Identical dataflow to ``_banked_bag_kernel`` except the table is the
    quant package's ``(R, row_bytes)`` int8 payload: each DMA moves one
    row's byte slot HBM->VMEM, and the accumulate step dequantizes it to
    fp32 on the fly using the row's ``tier`` and ``scale`` — both
    scalar-prefetched alongside the remap stream, so the dequant parameters
    are known from SMEM before the row's bytes land. The fp32 dequant math
    is ``quant.quantize.dequant_rows_f32``, the SAME function the jnp
    fallback runs, which is what makes kernel-vs-fallback parity bit-exact.

    ``scale_ref`` carries fp32 scales BITCAST to int32 (the scalar-prefetch
    stream stays integer-typed like the remap vectors); the kernel bitcasts
    each scalar back.
    """
    from repro.quant.quantize import dequant_rows_f32
    b0 = pl.program_id(0) * tile_b
    src_fn, meta_fn = _entry_fns(idx_ref, bank_ref, slot_ref, off_ref,
                                 my_ref[0], b0, bag_len, n_fields)

    def row_fn(e, raw):
        s = src_fn(e)
        scale = jax.lax.bitcast_convert_type(scale_ref[s], jnp.float32)
        tier = tier_ref[s]
        val = dequant_rows_f32(raw, scale, tier, dim, hot_dtype)
        # the row is rounded to fp32 before it is accumulated, as in the jnp
        # fallback: a xor with a zero no compiler can prove (tiers are >= 0)
        # keeps XLA:CPU, in interpret mode, from contracting the dequant
        # multiply and the accumulate add into one FMA
        bits = (jax.lax.bitcast_convert_type(val, jnp.int32)
                ^ jnp.minimum(tier, 0))
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    acc = jnp.zeros((tile_b, dim), jnp.float32)
    acc = _dma_accumulate(acc, payload_ref, buf, sem, 0, tile_b * bag_len,
                          src_fn, meta_fn, row_fn=row_fn)
    out_ref[...] = acc.astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# backward: sorted-run scatter-add (the transpose of the bag sum, in-kernel)
# ---------------------------------------------------------------------------
#
# The training backward streams each bag's cotangent row back onto every
# owned table slot its entries touched. A naive near-memory scatter would
# race whenever two entries of one tile share a slot (duplicate ids inside a
# bag, or across bags of the same tile); TPUs have no HBM atomics. Instead
# the traced prep walks the same (bank, slot, ownership, offsets) metadata
# as the forward to label every entry with its destination slot, sorts the
# entry stream by that slot, and hands the kernel scalar-prefetched views of
# the sorted order:
#
#   bag_sorted  (E,)    cotangent row (bag id) per sorted position
#   run_of      (E,)    run id per sorted position — a "run" is a maximal
#                       group of entries sharing one destination slot
#   run_starts  (S+1,)  first sorted position of each run; empty tail runs
#                       collapse to [n_valid, n_valid)
#   run_slot    (S,)    destination table row of each run
#   n_run       (1,)    number of live runs
#
# Every slot is touched by exactly ONE run and each grid step owns whole
# runs, so tiles never write the same output row — collision resolution
# costs a sort, not atomics. Within a tile, colliding entries accumulate
# into a (tile_s, D) fp32 VMEM accumulator (one row per run) while their
# cotangent rows stream in through the same two-slot DMA ping-pong as the
# forward; the finished rows stream OUT through a second ping-pong,
# overlapping the write-back of run i with the staging of run i+1.
# Untouched table rows must stay zero, so the d_table output is
# input_output_aliased to a zeros array.
#
# The kernel reads only arrays DERIVED from the sort permutation
# (bag_sorted = bags[perm], run_slot = dest[perm][starts]), never the raw
# ``argsort`` output itself: element-wise loads of an argsort result from
# inside the grid loop miscompile on XLA CPU for SPMD partitions > 0 (the
# shard_map path of this very backward; jax 0.4.x host platform), while
# vectorized gathers of the same permutation are fine — so the permutation
# is applied once in the prep and only its products cross into SMEM.

def scatter_run_metadata(dest: jax.Array, bags: jax.Array, n_rows: int,
                         n_runs_pad: int) -> tuple[jax.Array, ...]:
    """Slot-sorted scatter metadata (the backward kernel's prep stage).

    ``dest`` (E,) int32 holds each entry's destination table slot, or any
    value >= ``n_rows`` for entries that scatter nothing (-1 padding,
    foreign-bank rows); ``bags`` (E,) the cotangent row each entry drags in.
    Returns ``(bag_sorted, run_of, run_starts, run_slot, n_run)`` with the
    run axis padded to ``n_runs_pad`` (>= E, so the grid tiles it
    statically). Entry order is preserved within a run (stable sort) — the
    scatter accumulates per slot in the same order as the XLA fallback,
    which is what makes fp32 parity bit-exact.
    """
    E = dest.shape[0]
    assert n_runs_pad >= E, (n_runs_pad, E)
    perm = jnp.argsort(dest, stable=True).astype(jnp.int32)
    sd = jnp.take(dest, perm)
    bag_sorted = jnp.take(bags, perm).astype(jnp.int32)
    live = sd < n_rows
    n_valid = live.sum().astype(jnp.int32)
    prev = jnp.concatenate([jnp.full((1,), -1, sd.dtype), sd[:-1]])
    new_run = (sd != prev) & live
    n_run = new_run.sum().astype(jnp.int32)
    run_of = jnp.clip(jnp.cumsum(new_run) - 1, 0, None).astype(jnp.int32)
    starts = jnp.sort(jnp.where(new_run, jnp.arange(E, dtype=jnp.int32), E))
    pad = jnp.full((n_runs_pad + 1 - E,), E, jnp.int32)
    run_starts = jnp.minimum(jnp.concatenate([starts, pad]), n_valid)
    # dead runs get an in-bounds row; the n_run guard skips their write
    run_slot = jnp.minimum(sd, n_rows - 1)[
        jnp.minimum(run_starts[:-1], E - 1)].astype(jnp.int32)
    return bag_sorted, run_of, run_starts, run_slot, n_run.reshape(1)


def _ct_scatter_kernel(bag_sorted_ref, run_of_ref, run_starts_ref,
                       run_slot_ref, n_run_ref, ct_ref, dtab_in_ref,
                       dtab_ref, in_buf, in_sem, out_buf, out_sem, *,
                       tile_s: int, dim: int):
    """Grid step t owns runs [s0, s0 + tile_s): stream the runs' cotangent
    rows in (double-buffered), accumulate per run in fp32, stream the
    finished rows out to their table slots (double-buffered). Validity and
    ownership were folded into run membership by the prep sort, so every
    walked entry scatters. ``dtab_in_ref`` is the aliased zeros input — the
    kernel writes through ``dtab_ref`` only."""
    del dtab_in_ref
    s0 = pl.program_id(0) * tile_s
    n_run = n_run_ref[0]

    acc = jnp.zeros((tile_s, dim), jnp.float32)
    acc = _dma_accumulate(acc, ct_ref, in_buf, in_sem,
                          run_starts_ref[s0], run_starts_ref[s0 + tile_s],
                          lambda p: bag_sorted_ref[p],
                          lambda p: (run_of_ref[p] - s0, True))

    # accumulated-row DMA out: two-slot ping-pong (run i's copy is in
    # flight while run i+1's row is staged). Runs are packed to the front
    # globally, so 'run s is live' is the prefix test s < n_run — start and
    # wait guards agree by construction and the semaphores stay balanced.
    def dma(i, slot):
        return pltpu.make_async_copy(
            out_buf.at[slot], dtab_ref.at[pl.ds(run_slot_ref[s0 + i], 1), :],
            out_sem.at[slot])

    for i in range(tile_s):
        slot = i % 2
        if i >= 2:
            @pl.when(s0 + i - 2 < n_run)
            def _(i=i, slot=slot):
                dma(i - 2, slot).wait()

        @pl.when(s0 + i < n_run)
        def _(i=i, slot=slot):
            out_buf[slot] = acc[i][None].astype(out_buf.dtype)
            dma(i, slot).start()

    for i in range(max(tile_s - 2, 0), tile_s):
        @pl.when(s0 + i < n_run)
        def _(i=i):
            dma(i, i % 2).wait()


def _csr_bag_kernel(idx_ref, seg_ref, offs_ref, bank_ref, slot_ref, my_ref,
                    table_ref, out_ref, buf, sem, *, tile_b: int, dim: int):
    """CSR-ragged bags: entries for bags [b0, b0+tile_b) are the contiguous
    index range [offs[b0], offs[b0+tile_b]); per-entry bag = seg[e]."""
    b0 = pl.program_id(0) * tile_b
    my = my_ref[0]

    def resolve(e):
        raw = idx_ref[e]
        valid = raw >= 0
        row = jnp.where(valid, raw, 0)
        mine = valid & ((my < 0) | (bank_ref[row] == my))
        return row, mine

    def src_fn(e):
        row, mine = resolve(e)
        return jnp.where(mine, slot_ref[row], 0)

    def meta_fn(e):
        _, mine = resolve(e)
        return seg_ref[e] - b0, mine

    acc = jnp.zeros((tile_b, dim), jnp.float32)
    acc = _dma_accumulate(acc, table_ref, buf, sem,
                          offs_ref[b0], offs_ref[b0 + tile_b],
                          src_fn, meta_fn)
    out_ref[...] = acc.astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers (shape plumbing only — padding stays in the callers)
# ---------------------------------------------------------------------------

def _out_struct(shape, dtype, operands) -> jax.ShapeDtypeStruct:
    """A kernel's output type: varying over every manual mesh axis its
    operands vary over, which ``jax.shard_map`` checks (no axes outside
    one)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _scratch(dim: int, dtype, n_slots: int | None = 2):
    """Row-DMA scratch: ``n_slots`` (1, dim) VMEM slots + matching DMA
    semaphores (None: the two-slot ping-pong). The accumulate loops read
    the pipeline depth off the buffer shape, so this is the single knob the
    autotuner turns."""
    n_slots = 2 if n_slots is None else n_slots
    assert n_slots >= 1, n_slots
    return [pltpu.VMEM((n_slots, 1, dim), dtype),
            pltpu.SemaphoreType.DMA((n_slots,))]


def banked_embedding_bag_pallas(table: jax.Array, bank: jax.Array,
                                slot: jax.Array, field_offsets: jax.Array,
                                my_bank: jax.Array, idx: jax.Array, *,
                                tile_b: int = 8, interpret: bool = False,
                                k_max: int = 1, n_slots: int | None = None
                                ) -> jax.Array:
    """One bank's stage-2 partial bag sums.

    table (R, D) local rows in HBM; bank/slot (V,) int32 remap;
    field_offsets (F,) int32; my_bank (1,) int32 (< 0 disables the ownership
    test); idx (NB, L) int32 raw per-field ids, -1 padded. -> (NB, D).

    Offsets, remap and ownership resolve outside the kernel
    (``resolve_entries``, XLA gathers), so the kernel sees only each tile's
    block of local rows: its SMEM holds ``tile_b * L`` entries whatever the
    vocab or the batch. ``k_max > 1`` serves a REPLICATED table: bank/slot
    are the flattened ``(V * k_max,)`` replica-axis remap and each bag reads
    replica column ``wang_hash(bag) % k_max``. ``n_slots`` as in
    ``embedding_bag_pallas``.
    """
    rows = resolve_entries(idx, bank, slot, field_offsets, my_bank, k_max)
    return embedding_bag_pallas(table, rows, tile_b=tile_b,
                                interpret=interpret, n_slots=n_slots)


def tiered_embedding_bag_pallas(payload: jax.Array, scale_bits: jax.Array,
                                tier: jax.Array, bank: jax.Array,
                                slot: jax.Array, field_offsets: jax.Array,
                                my_bank: jax.Array, idx: jax.Array, *,
                                dim: int, hot_dtype: str = "bf16",
                                tile_b: int = 8, interpret: bool = False,
                                n_slots: int = 2) -> jax.Array:
    """One bank's stage-2 partial bag sums over a TIERED byte payload.

    payload (R, row_bytes) int8 rows in HBM (each DMA slot is sized for the
    HOT tier's width — quantized rows use a prefix of it, packed int4 a
    quarter); scale_bits (R,) int32 = fp32 per-row scales bitcast for the
    scalar-prefetch stream; tier (R,) int32 tier codes; bank/slot (V,) the
    remap; idx (NB, L) raw per-field ids, -1 padded. -> (NB, dim) fp32.
    """
    NB, L = idx.shape
    assert NB % tile_b == 0, (NB, tile_b)
    kernel = functools.partial(
        _tiered_bag_kernel, tile_b=tile_b, bag_len=L,
        n_fields=field_offsets.shape[0], dim=dim, hot_dtype=hot_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(NB // tile_b,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile_b, dim), lambda b, *_: (b, 0)),
        scratch_shapes=_scratch(payload.shape[-1], payload.dtype, n_slots),
    )
    args = (idx.reshape(-1), bank, slot, field_offsets, my_bank, tier,
            scale_bits, payload)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=_out_struct((NB, dim), jnp.float32, args),
        interpret=interpret, name="updlrm_tiered_bag",
    )(*args)


def embedding_bag_pallas(table: jax.Array, idx: jax.Array, *,
                         tile_b: int = 8, interpret: bool = False,
                         n_slots: int | None = None) -> jax.Array:
    """Plain bag sum: table (V, D); idx (B, L) table rows, -1 padded ->
    (B, D).

    Each grid step gets its tile's ``tile_b * L`` rows as an SMEM block, and
    the table stays in HBM in the ``pack_lanes`` layout: SMEM use is bounded
    by the tile, so any vocab and any batch fit, and HBM holds the table at
    its own size. ``n_slots`` is the row-copy ring's depth; None picks
    ``bag_ring_depth``'s.
    """
    packed, pack = pack_lanes(table)
    out = packed_bag_pallas(packed, pack, idx, tile_b=tile_b,
                            interpret=interpret, n_slots=n_slots)
    return out[:, :table.shape[1]]


def packed_bag_pallas(packed: jax.Array, pack: int, idx: jax.Array, *,
                      tile_b: int = 8, interpret: bool = False,
                      n_slots: int | None = None) -> jax.Array:
    """``embedding_bag_pallas`` over a table already in the ``pack_lanes``
    layout ``(packed, pack)``: -> (B, W) lane rows, ``W = packed.shape[1]``,
    each bag's sum in lanes [0, D)."""
    B, L = idx.shape
    assert B % tile_b == 0, (B, tile_b)
    W = packed.shape[1]
    n = tile_b * L
    depth = bag_ring_depth(n_slots, n, interpret)
    assert depth >= 2, depth
    # the largest divisor of BAG_GROUP that divides the tile's entries and
    # half the depth, so the ring holds two slot groups or more
    group = math.gcd(BAG_GROUP, depth // 2, n)
    kernel = functools.partial(_plain_bag_kernel, tile_b=tile_b, bag_len=L,
                               pack=pack, group=group)
    return pl.pallas_call(
        kernel, grid=(B // tile_b,),
        in_specs=[pl.BlockSpec((tile_b, L), lambda b: (b, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile_b, W), lambda b: (b, 0)),
        scratch_shapes=_scratch(W, packed.dtype, depth),
        out_shape=_out_struct((B, W), packed.dtype, (idx, packed)),
        interpret=interpret, name="updlrm_bag",
    )(idx, packed)


def plain_cache_bag_pallas(emt: jax.Array, cache: jax.Array,
                           cache_idx: jax.Array, residual_idx: jax.Array, *,
                           tile_b: int = 8, interpret: bool = False,
                           n_slots: int = 2) -> jax.Array:
    """Fig.-7 fused lookup over unbanked tables (identity layout): no remap
    operands in SMEM. -> (B, D) = Σ cached partials + Σ residual rows."""
    B, Lc = cache_idx.shape
    B2, Lr = residual_idx.shape
    assert B == B2 and B % tile_b == 0, (B, B2, tile_b)
    D = emt.shape[1]
    assert cache.shape[1] == D
    cache = cache.astype(emt.dtype)     # one scratch buffer, one row dtype
    kernel = functools.partial(_plain_fused_kernel, tile_b=tile_b, lc=Lc,
                               lr=Lr, dim=D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B // tile_b,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile_b, D), lambda b, *_: (b, 0)),
        scratch_shapes=_scratch(D, emt.dtype, n_slots),
    )
    args = (cache_idx.reshape(-1), residual_idx.reshape(-1),
            effective_lengths(cache_idx), effective_lengths(residual_idx),
            cache, emt)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=_out_struct((B, D), emt.dtype, args),
        interpret=interpret, name="updlrm_plain_cache_bag",
    )(*args)


def fused_cache_bag_pallas(emt: jax.Array, cache: jax.Array,
                           emt_bank: jax.Array, emt_slot: jax.Array,
                           cache_bank: jax.Array, cache_slot: jax.Array,
                           my_bank: jax.Array, cache_idx: jax.Array,
                           residual_idx: jax.Array, *, tile_b: int = 8,
                           interpret: bool = False,
                           n_slots: int = 2) -> jax.Array:
    """emt (R, D), cache (Rc, D); cache_idx (B, Lc), residual_idx (B, Lr)
    (-1 padded) -> (B, D) = Σ cached partials + Σ residual rows, one pass."""
    B, Lc = cache_idx.shape
    B2, Lr = residual_idx.shape
    assert B == B2 and B % tile_b == 0, (B, B2, tile_b)
    D = emt.shape[1]
    assert cache.shape[1] == D
    cache = cache.astype(emt.dtype)     # one scratch buffer, one row dtype
    kernel = functools.partial(_fused_cache_bag_kernel, tile_b=tile_b,
                               lc=Lc, lr=Lr, dim=D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=10,
        grid=(B // tile_b,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile_b, D), lambda b, *_: (b, 0)),
        scratch_shapes=_scratch(D, emt.dtype, n_slots),
    )
    args = (cache_idx.reshape(-1), residual_idx.reshape(-1),
            effective_lengths(cache_idx), effective_lengths(residual_idx),
            cache_bank, cache_slot, emt_bank, emt_slot, my_bank,
            jnp.zeros((1,), jnp.int32), cache, emt)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=_out_struct((B, D), emt.dtype, args),
        interpret=interpret, name="updlrm_fused_cache_bag",
    )(*args)


def _scatter_scratch(dim: int, ct_dtype, out_dtype,
                     n_slots: int | None = 2):
    """Backward scratch: the cotangent INPUT stream shares the N-slot
    ``_dma_accumulate`` pipeline (``_scratch``), but the accumulated-row
    OUTPUT ping-pong in ``_ct_scatter_kernel`` is hard-coded two-deep (its
    start/wait guards are written against slot reuse at distance 2), so that
    pair stays (2, ...)."""
    return [*_scratch(dim, ct_dtype, n_slots),
            pltpu.VMEM((2, 1, dim), out_dtype),
            pltpu.SemaphoreType.DMA((2,))]


def _dest_slots(rows: jax.Array, n_rows: int) -> jax.Array:
    """The race-freedom invariant, in ONE place: an entry scatters iff
    ``resolve_entries`` gave it a local row (valid AND owned); everything
    else gets the out-of-range sentinel that sorts it out of every run."""
    return jnp.where(rows >= 0, rows, n_rows)


def _ct_scatter_call(ct: jax.Array, dest: jax.Array, bags: jax.Array,
                     n_rows: int, out_dtype, *, tile_s: int,
                     interpret: bool, n_slots: int | None = 2) -> jax.Array:
    """Shared pallas_call plumbing for the backward scatters: run the sort
    prep, then the sorted-run kernel with the d_table aliased to zeros."""
    E = dest.shape[0]
    n_tiles = max(1, -(-E // tile_s))
    bag_sorted, run_of, run_starts, run_slot, n_run = scatter_run_metadata(
        dest, bags, n_rows, n_tiles * tile_s)
    ctp, d = (ct, ct.shape[-1]) if interpret else pad_last_dim(ct)
    D = ctp.shape[-1]
    kernel = functools.partial(_ct_scatter_kernel, tile_s=tile_s, dim=D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=_scatter_scratch(D, ctp.dtype, out_dtype, n_slots),
    )
    args = (bag_sorted, run_of, run_starts, run_slot, n_run, ctp)
    out_type = _out_struct((n_rows, D), out_dtype, args)
    zeros = jnp.zeros((n_rows, D), out_dtype)
    if out_type.vma:
        zeros = jax.lax.pcast(zeros, tuple(sorted(out_type.vma)),
                              to="varying")
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=out_type,
        # d_table aliases a zeros input (operand 6 = 5 scalars + ct): only
        # touched rows are DMA'd, the rest must already BE zero
        input_output_aliases={6: 0},
        interpret=interpret, name="updlrm_ct_scatter",
    )(*args, zeros)
    return out[:, :d]


def ct_scatter_bag_pallas(ct: jax.Array, idx: jax.Array, bank: jax.Array,
                          slot: jax.Array, field_offsets: jax.Array,
                          my_bank: jax.Array, n_rows: int, out_dtype, *,
                          tile_s: int = 8, interpret: bool = False,
                          k_max: int = 1, n_slots: int | None = 2
                          ) -> jax.Array:
    """Transpose of ``banked_embedding_bag_pallas``: scatter-add the bag
    cotangents back onto one bank's rows, entirely in the kernel layer.

    ct (NB, D) cotangent rows; idx (NB, L) the forward's raw per-field ids
    (-1 padded); bank/slot (V,) the replicated remap; field_offsets (F,);
    my_bank (1,) int32 (< 0: own everything). -> d_table (n_rows, D).

    The prep enumerates entries j-major (e = j*NB + bag: position-major like
    the jnp fallback's scan over L), walks the same remap + ownership +
    offset metadata as the forward to label each entry with its destination
    slot, and sorts — see ``scatter_run_metadata``. fp32 accumulation per
    run, one cast to ``out_dtype`` at the write, matching the fallback's
    accumulation policy bit-for-bit in fp32.

    ``k_max > 1`` is the k-way replicated backward: each entry's destination
    is the SAME hash-picked copy its forward read came through (bank/slot
    flattened ``(V * k_max,)``), so every copy of a row accumulates exactly
    the cotangents of the bags it served — the sorted-run machinery groups
    the per-copy collisions like any other slot collision, and summing a
    row's copies recovers the single-copy gradient.
    """
    NB, L = idx.shape
    rows = resolve_entries(idx, bank, slot, field_offsets, my_bank,
                           k_max).T.reshape(-1)              # j-major
    bag = jnp.arange(NB * L, dtype=jnp.int32) % NB
    return _ct_scatter_call(ct, _dest_slots(rows, n_rows), bag, n_rows,
                            out_dtype,
                            tile_s=tile_s, interpret=interpret,
                            n_slots=n_slots)


def ct_scatter_csr_pallas(ct: jax.Array, indices: jax.Array,
                          seg_ids: jax.Array, bank: jax.Array,
                          slot: jax.Array, my_bank: jax.Array, n_rows: int,
                          out_dtype, *, tile_s: int = 8,
                          interpret: bool = False,
                          n_slots: int = 2) -> jax.Array:
    """Transpose of ``csr_bag_pallas``: ct (num_bags, D) bag cotangents,
    indices/seg_ids (T,) the forward's flat stream (entries keep their
    natural stream order within a run — the single-scatter fallback's
    order). -> (n_rows, D)."""
    rows = resolve_entries(indices[:, None], bank, slot,
                           jnp.zeros((1,), jnp.int32), my_bank)[:, 0]
    return _ct_scatter_call(ct, _dest_slots(rows, n_rows), seg_ids, n_rows,
                            out_dtype,
                            tile_s=tile_s, interpret=interpret,
                            n_slots=n_slots)


def csr_bag_pallas(table: jax.Array, bank: jax.Array, slot: jax.Array,
                   my_bank: jax.Array, indices: jax.Array, seg_ids: jax.Array,
                   offsets_ext: jax.Array, num_bags: int, *, tile_b: int = 8,
                   interpret: bool = False, n_slots: int = 2) -> jax.Array:
    """CSR bag sums: indices (T,) flat stream, seg_ids (T,) bag per entry,
    offsets_ext (num_bags + 1,) with offsets_ext[-1] == T. -> (num_bags, D).
    ``num_bags`` must be a multiple of tile_b (pad offsets with T)."""
    T = indices.shape[0]
    R, D = table.shape
    assert num_bags % tile_b == 0, (num_bags, tile_b)
    assert offsets_ext.shape[0] == num_bags + 1
    kernel = functools.partial(_csr_bag_kernel, tile_b=tile_b, dim=D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(num_bags // tile_b,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile_b, D), lambda b, *_: (b, 0)),
        scratch_shapes=_scratch(D, table.dtype, n_slots),
    )
    args = (indices, seg_ids, offsets_ext, bank, slot, my_bank, table)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=_out_struct((num_bags, D), table.dtype, args),
        interpret=interpret, name="updlrm_csr_bag",
    )(*args)
