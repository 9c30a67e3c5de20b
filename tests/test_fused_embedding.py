"""Parity tests for the fused production lookup path (ISSUE 1 tentpole):

pallas (interpret) backend vs the jnp scan backend vs the kernels/ref.py
oracles — multi-field bags with in-kernel offsets, fused cache+residual,
CSR-ragged bags, and the custom_vjp gradient vs jax.grad of the reference —
across fp32/bf16 tables and odd (non-128-multiple) D.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.embedding import (BankedTable, banked_cache_residual_bag,
                                  banked_embedding_bag, csr_embedding_bag,
                                  pack_replicated, pack_table,
                                  replicated_embedding_bag)
from repro.core.partitioning import (non_uniform_partition,
                                     replicated_partition, uniform_partition)
from repro.kernels import ref as REF
from repro.kernels.embedding_bag import BAG_RING_DEPTH, bag_ring_depth


def _banked(rng, v, d, banks, dtype=jnp.float32):
    table = rng.standard_normal((v, d)).astype(np.float32)
    plan = non_uniform_partition(rng.random(v) + 0.1, banks)
    return table, pack_table(table, plan, dtype=dtype)


def _multihot(rng, b, f, l, vocab_sizes):
    idx = np.full((b, f, l), -1, np.int32)
    for bb in range(b):
        for ff in range(f):
            n = rng.integers(0, l + 1)
            idx[bb, ff, :n] = rng.integers(0, vocab_sizes[ff], n)
    return jnp.asarray(idx)


@pytest.mark.parametrize("d", [16, 33, 128])       # incl. odd D
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_multifield_pallas_matches_jnp_and_ref(d, dtype):
    rng = np.random.default_rng(d)
    vocab_sizes = (40, 30, 30)
    v = sum(vocab_sizes)
    offs = np.concatenate([[0], np.cumsum(vocab_sizes)[:-1]]).astype(np.int32)
    table, bt = _banked(rng, v, d, banks=4, dtype=dtype)
    idx = _multihot(rng, 9, 3, 5, vocab_sizes)
    fo = jnp.asarray(offs)

    got_p = banked_embedding_bag(bt, idx, None, backend="pallas",
                                 field_offsets=fo)
    got_j = banked_embedding_bag(bt, idx, None, backend="jnp",
                                 field_offsets=fo)
    # oracle: offset rows through the reference bag sum on the raw table
    rows = jnp.where(idx >= 0, idx + fo[None, :, None], -1)
    want = REF.embedding_bag_ref(
        jnp.asarray(table, dtype), rows.reshape(-1, idx.shape[-1])
    ).reshape(got_p.shape)

    atol = 1e-5 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(got_p, np.float32),
                               np.asarray(got_j, np.float32), atol=atol)
    np.testing.assert_allclose(np.asarray(got_p, np.float32),
                               np.asarray(want, np.float32), atol=atol)


# ---------------------------------------------------------------------------
# the updlrm_bag row-copy ring: every depth sums each bag in the same order
# ---------------------------------------------------------------------------

RING_L = 6                      # a tile of 8 bags holds 48 entries
# the ping-pong, odd (groups of 1), groups of 4, the chosen depth (groups
# of 8), and a ring deeper than a tile's entries
RING_DEPTHS = [2, 3, 24, BAG_RING_DEPTH, 8 * RING_L + 3]


def _ring_bags(rng, pattern, n, f, vocab):
    """(n, f, RING_L) per-field ids: ``all_pad`` empties every third bag
    and pads the rest at the end, ``holes`` puts -1s between valid ids."""
    idx = rng.integers(0, vocab, (n, f, RING_L)).astype(np.int32)
    if pattern == "all_pad":
        lens = rng.integers(1, RING_L + 1, (n, f))
        lens.reshape(-1)[::3] = 0
        idx[np.arange(RING_L) >= lens[..., None]] = -1
    else:
        idx[rng.random(idx.shape) < 0.4] = -1
        idx[..., -1] = rng.integers(0, vocab, (n, f))   # holes stay interior
    return jnp.asarray(idx)


@pytest.mark.parametrize("k_max", [1, 2])
@pytest.mark.parametrize("pattern", ["all_pad", "holes"])
@pytest.mark.parametrize("n_slots", RING_DEPTHS)
def test_bag_ring_depth_bit_exact_to_scan(n_slots, pattern, k_max):
    """The pallas forward at every ring depth and copy group in
    ``RING_DEPTHS`` equals the jnp scan bit for bit: the ring changes when
    rows arrive, never the j-ascending fp32 order they are added in."""
    rng = np.random.default_rng(100 * n_slots + k_max)
    vocab_sizes, d, banks = (40, 30, 26), 16, 4
    v = sum(vocab_sizes)
    fo = jnp.asarray(np.concatenate([[0], np.cumsum(vocab_sizes)[:-1]]),
                     jnp.int32)
    table = rng.standard_normal((v, d)).astype(np.float32)
    freq = rng.random(v) + 0.1
    idx = _ring_bags(rng, pattern, 7, len(vocab_sizes), min(vocab_sizes))
    if k_max == 1:
        bt = pack_table(table, non_uniform_partition(freq, banks))

        def lookup(backend, **kw):
            return banked_embedding_bag(bt, idx, None, backend=backend,
                                        field_offsets=fo, **kw)
    else:
        copies = np.where(freq > np.quantile(freq, 0.8), k_max, 1)
        rt = pack_replicated(table, replicated_partition(
            freq, banks, copies=copies.astype(np.int32), k_max=k_max))

        def lookup(backend, **kw):
            return replicated_embedding_bag(rt, idx, None, backend=backend,
                                            field_offsets=fo, **kw)
    got = lookup("pallas", n_slots=n_slots, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(lookup("jnp")))


@pytest.mark.parametrize("n_slots,entries,interpret,want", [
    (None, 2048, False, BAG_RING_DEPTH),      # compiled: the deep ring
    (None, 4, False, 4),                      # capped at a tile's entries
    (None, 2048, True, 2),                    # interpret: the ping-pong
    (3, 2048, False, 3),                      # an explicit depth wins
    (99, 4, True, 99),
])
def test_bag_ring_depth_choice(n_slots, entries, interpret, want):
    assert bag_ring_depth(n_slots, entries, interpret) == want


@pytest.mark.parametrize("d", [8, 33])
def test_fused_cache_residual_matches_ref(d):
    rng = np.random.default_rng(d + 1)
    v, nc = 80, 24
    table, bt = _banked(rng, v, d, banks=4)
    ctab_raw = rng.standard_normal((nc, d)).astype(np.float32)
    cbt = pack_table(ctab_raw, uniform_partition(nc, 2))
    ci = jnp.asarray(rng.integers(-1, nc, (10, 3, 4)), jnp.int32)
    ri = jnp.asarray(rng.integers(-1, v, (10, 3, 6)), jnp.int32)

    got_p = banked_cache_residual_bag(bt, cbt, ci, ri, None,
                                      backend="pallas")
    got_j = banked_cache_residual_bag(bt, cbt, ci, ri, None, backend="jnp")
    want = REF.cache_bag_ref(
        jnp.asarray(table), jnp.asarray(ctab_raw),
        ci.reshape(-1, ci.shape[-1]), ri.reshape(-1, ri.shape[-1])
    ).reshape(got_p.shape)

    np.testing.assert_allclose(np.asarray(got_p), np.asarray(got_j),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(want),
                               atol=1e-4)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_multifield_grads_match_reference(backend):
    """custom_vjp scatter-add backward == jax.grad of the reference path."""
    rng = np.random.default_rng(3)
    vocab_sizes = (20, 22)
    v, d = sum(vocab_sizes), 24
    offs = np.concatenate([[0], np.cumsum(vocab_sizes)[:-1]]).astype(np.int32)
    table, bt = _banked(rng, v, d, banks=4)
    idx = _multihot(rng, 8, 2, 5, vocab_sizes)
    fo = jnp.asarray(offs)

    def loss(packed):
        t2 = dataclasses.replace(bt, packed=packed)
        return (banked_embedding_bag(t2, idx, None, backend=backend,
                                     field_offsets=fo) ** 2).sum()

    def loss_ref(packed):
        t2 = dataclasses.replace(bt, packed=packed)
        rows = jnp.where(idx >= 0, idx + fo[None, :, None], -1)
        flat = t2.remap_bank * t2.rows_per_bank + t2.remap_slot
        safe = jnp.where(rows >= 0, rows, 0)
        g = jnp.take(packed, flat[safe], axis=0)
        g = jnp.where((rows >= 0)[..., None], g, 0)
        return (g.sum(-2) ** 2).sum()

    np.testing.assert_allclose(loss(bt.packed), loss_ref(bt.packed),
                               rtol=1e-5)
    got = jax.grad(loss)(bt.packed)
    want = jax.grad(loss_ref)(bt.packed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_fused_cache_residual_grads():
    """Gradients flow to BOTH tables through the fused kernel."""
    rng = np.random.default_rng(4)
    v, nc, d = 50, 12, 16
    table, bt = _banked(rng, v, d, banks=2)
    ctab_raw = rng.standard_normal((nc, d)).astype(np.float32)
    cbt = pack_table(ctab_raw, uniform_partition(nc, 2))
    ci = jnp.asarray(rng.integers(-1, nc, (8, 4)), jnp.int32)
    ri = jnp.asarray(rng.integers(-1, v, (8, 6)), jnp.int32)

    def loss(emt_packed, cache_packed, backend):
        t2 = dataclasses.replace(bt, packed=emt_packed)
        c2 = dataclasses.replace(cbt, packed=cache_packed)
        return (banked_cache_residual_bag(t2, c2, ci, ri, None,
                                          backend=backend) ** 2).sum()

    ge_p, gc_p = jax.grad(loss, argnums=(0, 1))(bt.packed, cbt.packed,
                                                "pallas")
    ge_j, gc_j = jax.grad(loss, argnums=(0, 1))(bt.packed, cbt.packed, "jnp")
    np.testing.assert_allclose(np.asarray(ge_p), np.asarray(ge_j), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gc_p), np.asarray(gc_j), atol=1e-4)
    assert float(jnp.abs(gc_p).sum()) > 0     # cache table really trains


def test_bf16_table_grads_accumulate_fp32():
    """Colliding scatter-adds onto a hot row must not round away in bf16:
    the custom_vjp accumulates fp32 and casts once at the end. 300 hits of
    cotangent 1.0 on one row => grad exactly 300 (bf16 sequential adds would
    stall near 256, where the ulp is 2)."""
    rng = np.random.default_rng(0)
    v, d, b, l = 16, 8, 25, 12
    table, bt = _banked(rng, v, d, banks=2, dtype=jnp.bfloat16)
    idx = jnp.zeros((b, l), jnp.int32)            # every entry hits row 0

    def loss(packed):
        t2 = dataclasses.replace(bt, packed=packed)
        return banked_embedding_bag(t2, idx, None, backend="pallas").sum()

    g = jax.grad(loss)(bt.packed)
    hot = int(bt.remap_bank[0]) * bt.rows_per_bank + int(bt.remap_slot[0])
    np.testing.assert_allclose(np.asarray(g, np.float32)[hot],
                               np.full(d, b * l, np.float32))


# ---------------------------------------------------------------------------
# Pallas backward (sorted-run scatter kernel) vs the XLA scatter fallback
# (ISSUE 3 tentpole): same pallas forward, bwd_backend='pallas' vs 'jnp'.
# fp32 must BIT-match (the prep's stable slot-sort preserves the fallback's
# per-slot accumulation order); bf16 tolerance-matches (both accumulate
# fp32, cast once).
# ---------------------------------------------------------------------------

def _grad_pair(loss_of_bwd, *args):
    gp = jax.grad(lambda *a: loss_of_bwd("pallas", *a), argnums=tuple(
        range(len(args))))(*args)
    gj = jax.grad(lambda *a: loss_of_bwd("jnp", *a), argnums=tuple(
        range(len(args))))(*args)
    return gp, gj


def _assert_bwd_match(gp, gj, dtype):
    for p, j in zip(jax.tree.leaves(gp), jax.tree.leaves(gj)):
        if dtype == jnp.float32:
            np.testing.assert_array_equal(np.asarray(p), np.asarray(j))
        else:
            np.testing.assert_allclose(np.asarray(p, np.float32),
                                       np.asarray(j, np.float32), atol=0.3)


@pytest.mark.parametrize("d", [16, 33, 128])       # incl. odd D
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_bwd_rect_sweep(d, dtype):
    """Rectangular multi-field path: kernel scatter == XLA scatter."""
    rng = np.random.default_rng(d + 100)
    vocab_sizes = (40, 30, 30)
    v = sum(vocab_sizes)
    offs = np.concatenate([[0], np.cumsum(vocab_sizes)[:-1]]).astype(np.int32)
    table, bt = _banked(rng, v, d, banks=4, dtype=dtype)
    idx = _multihot(rng, 9, 3, 5, vocab_sizes)
    fo = jnp.asarray(offs)

    def loss(bwd, packed):
        t2 = dataclasses.replace(bt, packed=packed)
        return (banked_embedding_bag(t2, idx, None, backend="pallas",
                                     bwd_backend=bwd,
                                     field_offsets=fo) ** 2).sum()

    gp, gj = _grad_pair(loss, bt.packed)
    _assert_bwd_match(gp, gj, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_bwd_collisions_in_tile(dtype):
    """The case the in-VMEM accumulator must get right: the same row
    duplicated WITHIN a bag and ACROSS bags of the same tile (tile_b=8, so
    bags 0..7 collide in one grid step), plus a -1 hole inside a bag."""
    rng = np.random.default_rng(5)
    v, d, b, l = 24, 16, 8, 6
    table, bt = _banked(rng, v, d, banks=2, dtype=dtype)
    idx = np.asarray(rng.integers(0, v, (b, l)), np.int32)
    idx[:, 0] = 3                  # every bag hits row 3 (cross-bag)
    idx[0, 1:4] = 3                # bag 0 hits it 3 more times (in-bag)
    idx[2, 2] = -1                 # interior hole stays masked
    idx = jnp.asarray(idx)

    def loss(bwd, packed):
        t2 = dataclasses.replace(bt, packed=packed)
        return (banked_embedding_bag(t2, idx, None, backend="pallas",
                                     bwd_backend=bwd) ** 2).sum()

    gp, gj = _grad_pair(loss, bt.packed)
    _assert_bwd_match(gp, gj, dtype)
    # the hot row really saw every colliding contribution
    hot = int(bt.remap_bank[3]) * bt.rows_per_bank + int(bt.remap_slot[3])
    assert float(jnp.abs(jnp.asarray(gp[0], jnp.float32)[hot]).sum()) > 0


@pytest.mark.parametrize("d", [8, 33])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_bwd_cache_residual_sweep(d, dtype):
    """Fused cache+residual: the DUAL scatter (one cotangent onto both the
    EMT and the cache table) matches the XLA fallback on both tables."""
    rng = np.random.default_rng(d + 200)
    v, nc = 80, 24
    table, bt = _banked(rng, v, d, banks=4, dtype=dtype)
    ctab_raw = rng.standard_normal((nc, d)).astype(np.float32)
    cbt = pack_table(ctab_raw, uniform_partition(nc, 2), dtype=dtype)
    ci = np.asarray(rng.integers(-1, nc, (10, 3, 4)), np.int32)
    ri = np.asarray(rng.integers(-1, v, (10, 3, 6)), np.int32)
    ci[:, 0, 0] = 1                # cache entry 1 collides across all bags
    ri[:, 1, 0] = 7                # EMT row 7 collides across all bags
    ci, ri = jnp.asarray(ci), jnp.asarray(ri)

    def loss(bwd, ep, cp):
        t2 = dataclasses.replace(bt, packed=ep)
        c2 = dataclasses.replace(cbt, packed=cp)
        return (banked_cache_residual_bag(t2, c2, ci, ri, None,
                                          backend="pallas",
                                          bwd_backend=bwd) ** 2).sum()

    gp, gj = _grad_pair(loss, bt.packed, cbt.packed)
    _assert_bwd_match(gp, gj, dtype)
    assert float(jnp.abs(jnp.asarray(gp[1], jnp.float32)).sum()) > 0


@pytest.mark.parametrize("d", [16, 33])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_bwd_csr_sweep(d, dtype):
    """CSR-ragged path: kernel scatter == the fallback's single scatter,
    duplicate rows across ragged bags included."""
    rng = np.random.default_rng(d + 300)
    v, total, num_bags = 64, 41, 7
    table, bt = _banked(rng, v, d, banks=4, dtype=dtype)
    indices = np.asarray(rng.integers(-1, v, (total,)), np.int32)
    indices[::5] = 11              # row 11 recurs through the flat stream
    indices = jnp.asarray(indices)
    cuts = np.sort(rng.choice(np.arange(1, total), num_bags - 1,
                              replace=False))
    offsets = jnp.asarray(np.concatenate([[0], cuts]), jnp.int32)

    def loss(bwd, packed):
        t2 = dataclasses.replace(bt, packed=packed)
        return (csr_embedding_bag(t2, indices, offsets, num_bags, None,
                                  backend="pallas",
                                  bwd_backend=bwd) ** 2).sum()

    gp, gj = _grad_pair(loss, bt.packed)
    _assert_bwd_match(gp, gj, dtype)


def test_bwd_backend_validation():
    with pytest.raises(ValueError, match="bwd_backend"):
        from repro.core.embedding import _resolve_bwd
        _resolve_bwd("kernel", "pallas")


@pytest.mark.parametrize("num_bags,total", [(7, 41), (8, 8), (5, 60)])
def test_csr_pallas_matches_jnp(num_bags, total):
    rng = np.random.default_rng(num_bags + total)
    v, d = 64, 20
    table, bt = _banked(rng, v, d, banks=4)
    indices = jnp.asarray(rng.integers(-1, v, (total,)), jnp.int32)
    cuts = np.sort(rng.choice(np.arange(1, total), num_bags - 1,
                              replace=False)) if num_bags > 1 else np.array([], int)
    offsets = jnp.asarray(np.concatenate([[0], cuts]), jnp.int32)

    got = csr_embedding_bag(bt, indices, offsets, num_bags, None,
                            backend="pallas")
    want = csr_embedding_bag(bt, indices, offsets, num_bags, None,
                             backend="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_forward_has_no_blfd_intermediate():
    """models/dlrm.py forward must not materialize a (B, F, L, D) gathered
    tensor on either backend — checked on the jaxpr of the traced forward."""
    from repro.models import dlrm as D
    cfg = D.DLRMConfig(name="t", vocab_sizes=(60, 60), embed_dim=16,
                       n_dense=4, bot_mlp=(8, 16), top_mlp=(8,), multi_hot=7)
    params, statics = D.init_params(cfg, jax.random.key(0))
    batch = {
        "dense": jnp.zeros((6, 4), jnp.float32),
        "sparse": jnp.asarray(
            np.random.default_rng(0).integers(-1, 60, (6, 2, 7)), jnp.int32),
    }
    B, F, L, d = 6, 2, 7, 16
    for backend in ("jnp", "pallas"):
        jaxpr = jax.make_jaxpr(
            lambda p: D.forward(cfg, p, statics, batch, None,
                                backend=backend))(params)
        shapes = {tuple(v.aval.shape) for eqn in jaxpr.jaxpr.eqns
                  for v in eqn.outvars}
        assert (B, F, L, d) not in shapes, backend
        assert (B * F, L, d) not in shapes, backend
