"""Multi-device distribution checks — run as a SUBPROCESS by test_dist.py so
the forced 8-device host platform never leaks into the main pytest process.

Each check compares a distributed execution (shard_map / GSPMD on the 4x2
mesh) against the single-device reference — numerically, not just shapes.
Exits nonzero on any mismatch.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.embedding import (DistCtx, banked_embedding_bag, pack_table)
from repro.core.partitioning import non_uniform_partition

P = jax.sharding.PartitionSpec
FAILED = []


def check(name, ok):
    print(("PASS " if ok else "FAIL ") + name, flush=True)
    if not ok:
        FAILED.append(name)


def mesh42():
    from repro.launch.mesh import make_mesh
    return make_mesh((4, 2), ("data", "model"))


def check_banked_lookup_distributed():
    rng = np.random.default_rng(0)
    V, D, banks = 64, 16, 2
    table = rng.standard_normal((V, D)).astype(np.float32)
    freq = rng.random(V) + 0.1
    plan = non_uniform_partition(freq, banks)
    bt = pack_table(table, plan)
    idx = jnp.array(rng.integers(-1, V, (8, 5)), jnp.int32)
    mesh = mesh42()
    dist = DistCtx(mesh=mesh, dp_axes=("data",))
    got = jax.jit(lambda t, i: banked_embedding_bag(t, i, dist))(bt, idx)
    want = banked_embedding_bag(bt, idx, None)
    check("banked_lookup_distributed", np.allclose(got, want, atol=1e-5))


def check_banked_lookup_grads():
    """d(loss)/d(packed) must match the single-device gradient — the banked
    table trains correctly through the psum combine."""
    rng = np.random.default_rng(1)
    V, D, banks = 32, 8, 2
    table = rng.standard_normal((V, D)).astype(np.float32)
    plan = non_uniform_partition(rng.random(V) + 0.1, banks)
    bt = pack_table(table, plan)
    idx = jnp.array(rng.integers(-1, V, (8, 4)), jnp.int32)
    mesh = mesh42()
    dist = DistCtx(mesh=mesh, dp_axes=("data",))

    def loss_d(packed):
        t2 = jax.tree.map(lambda x: x, bt)
        t2.packed = packed
        return banked_embedding_bag(t2, idx, dist).sum()

    def loss_l(packed):
        t2 = jax.tree.map(lambda x: x, bt)
        t2.packed = packed
        return banked_embedding_bag(t2, idx, None).sum()

    gd = jax.jit(jax.grad(loss_d))(bt.packed)
    gl = jax.grad(loss_l)(bt.packed)
    check("banked_lookup_grads", np.allclose(gd, gl, atol=1e-5))


def check_banked_pallas_backend():
    """Pallas stage 2 (interpret mode) INSIDE the shard_map == jnp backend,
    forward and gradient — the fused-kernel production path."""
    rng = np.random.default_rng(7)
    V, D, banks = 64, 16, 2
    table = rng.standard_normal((V, D)).astype(np.float32)
    plan = non_uniform_partition(rng.random(V) + 0.1, banks)
    bt = pack_table(table, plan)
    fo = jnp.array([0, 20, 40], jnp.int32)
    idx = jnp.array(rng.integers(-1, 20, (8, 3, 5)), jnp.int32)
    mesh = mesh42()
    dist = DistCtx(mesh=mesh, dp_axes=("data",))
    want = banked_embedding_bag(bt, idx, None, backend="jnp",
                                field_offsets=fo)
    got = jax.jit(lambda t, i: banked_embedding_bag(
        t, i, dist, backend="pallas", field_offsets=fo))(bt, idx)
    check("banked_pallas_backend_fwd",
          np.allclose(got, want, atol=1e-5))

    import dataclasses

    def loss(packed, backend, d):
        t2 = dataclasses.replace(bt, packed=packed)
        return (banked_embedding_bag(t2, idx, d, backend=backend,
                                     field_offsets=fo) ** 2).sum()

    gl = jax.grad(lambda p: loss(p, "jnp", None))(bt.packed)
    gd = jax.jit(jax.grad(lambda p: loss(p, "pallas", dist)))(bt.packed)
    check("banked_pallas_backend_grad", np.allclose(gd, gl, atol=1e-4))


def check_seqsharded_decode():
    from repro.dist.collectives import seqsharded_decode_attention
    rng = np.random.default_rng(2)
    B, S, Hq, Hkv, Dh = 4, 16, 4, 2, 8
    q = jnp.array(rng.standard_normal((B, Hq, Dh)), jnp.float32)
    kn = jnp.array(rng.standard_normal((B, Hkv, Dh)), jnp.float32)
    vn = jnp.array(rng.standard_normal((B, Hkv, Dh)), jnp.float32)
    kc = jnp.array(rng.standard_normal((B, S, Hkv, Dh)), jnp.float32)
    vc = jnp.array(rng.standard_normal((B, S, Hkv, Dh)), jnp.float32)
    pos = jnp.int32(7)
    mesh = mesh42()
    dist = DistCtx(mesh=mesh, dp_axes=("data",))
    o_d, kc_d, vc_d = jax.jit(
        lambda q, kn, vn, kc, vc: seqsharded_decode_attention(
            q, kn, vn, kc, vc, pos, dist=dist, seq_axes=("model",)))(
        q, kn, vn, kc, vc)
    o_l, kc_l, vc_l = seqsharded_decode_attention(
        q, kn, vn, kc, vc, pos, dist=None)
    ok = (np.allclose(o_d, o_l, atol=1e-4)
          and np.allclose(kc_d, kc_l, atol=1e-6)
          and np.allclose(vc_d, vc_l, atol=1e-6))
    check("seqsharded_decode", ok)
    # seq sharded over BOTH axes (the long_500k layout, batch replicated)
    dist2 = DistCtx(mesh=mesh, dp_axes=("data",))
    o_d2, kc_d2, _ = jax.jit(
        lambda q, kn, vn, kc, vc: seqsharded_decode_attention(
            q, kn, vn, kc, vc, pos, dist=dist2,
            seq_axes=("data", "model")))(q, kn, vn, kc, vc)
    check("seqsharded_decode_allaxes",
          np.allclose(o_d2, o_l, atol=1e-4)
          and np.allclose(kc_d2, kc_l, atol=1e-6))


def check_gat_edge_sharded():
    from repro.configs import get_arch
    from repro.data.synthetic import random_graph
    from repro.models import gat as G
    cfg = get_arch("gat-cora").reduced
    g = random_graph(40, 128, cfg.d_feat, cfg.n_classes, seed=3)
    batch = {k: jnp.asarray(v) for k, v in g.items()}
    batch["edge_mask"] = jnp.ones_like(batch["edge_src"], bool)
    params = G.init_params(cfg, jax.random.key(0))
    mesh = mesh42()
    dist = DistCtx(mesh=mesh, dp_axes=("data",))
    loss_d = jax.jit(lambda p: G.loss_full(cfg, p, batch, dist))(params)
    loss_l = G.loss_full(cfg, params, batch, None)
    check("gat_edge_sharded_loss", np.allclose(loss_d, loss_l, atol=1e-4))
    gd = jax.jit(jax.grad(lambda p: G.loss_full(cfg, p, batch, dist)))(params)
    gl = jax.grad(lambda p: G.loss_full(cfg, p, batch, None))(params)
    ok = all(np.allclose(a, b, atol=1e-4) for a, b in
             zip(jax.tree.leaves(gd), jax.tree.leaves(gl)))
    check("gat_edge_sharded_grads", ok)


def check_dp_compressed_step():
    from repro.configs import get_arch
    from repro.data.synthetic import dlrm_batch
    from repro.models import dlrm as D
    from repro.train.dp_step import build_dp_compressed_step
    from repro.train.optim import adam
    from repro.train.train_step import TrainState, build_train_step
    cfg = get_arch("dlrm-rm2").reduced
    params, statics = D.init_params(cfg, jax.random.key(0))
    mesh = mesh42()
    loss = lambda p, b: D.loss_fn(cfg, p, statics, b)
    opt = adam(1e-2)
    step_c = jax.jit(build_dp_compressed_step(loss, opt, mesh,
                                              ("data", "model")))
    state = TrainState.create(params, opt, compress=True)
    state_ref = TrainState.create(params, opt)
    step_r = jax.jit(build_train_step(loss, opt, clip_norm=None))
    losses_c, losses_r = [], []
    for i in range(15):
        b = dlrm_batch(cfg.vocab_sizes, cfg.n_dense, 64, seed=0, step=0)
        b = {k: jnp.asarray(v) for k, v in b.items()}
        state, mc = step_c(state, b)
        state_ref, mr = step_r(state_ref, b)
        losses_c.append(float(mc["loss"]))
        losses_r.append(float(mr["loss"]))
    # compressed training converges like uncompressed (within tolerance)
    check("dp_compressed_converges",
          losses_c[-1] < losses_c[0]
          and abs(losses_c[-1] - losses_r[-1]) < 0.15)


def check_csr_sharded_lookup():
    """Balanced-split CSR lookup (flat stream sharded over dp) == the
    replicating csr_embedding_bag, jnp and pallas stage 2."""
    from repro.core.embedding import (balanced_csr_shards,
                                      csr_embedding_bag,
                                      csr_embedding_bag_sharded)
    rng = np.random.default_rng(11)
    V, D, banks = 64, 16, 2
    table = rng.standard_normal((V, D)).astype(np.float32)
    plan = non_uniform_partition(rng.random(V) + 0.1, banks)
    bt = pack_table(table, plan)
    lens = rng.integers(1, 9, 13)
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    indices = rng.integers(0, V, int(offsets[-1])).astype(np.int32)
    mesh = mesh42()
    dist = DistCtx(mesh=mesh, dp_axes=("data",))
    bounds = balanced_csr_shards(offsets, dist.dp_size())
    totals = offsets[bounds[1:]] - offsets[bounds[:-1]]
    check("csr_split_balanced",
          totals.max() - totals.min() <= lens.max())
    want = csr_embedding_bag(bt, jnp.asarray(indices),
                             jnp.asarray(offsets[:13]), 13, None,
                             backend="jnp")
    for backend in ("jnp", "pallas"):
        got = csr_embedding_bag_sharded(bt, indices, offsets, 13, dist,
                                        backend=backend)
        check(f"csr_sharded_{backend}", np.allclose(got, want, atol=1e-5))
        # single-device fallback (dp collapses away) honors both offset forms
        got1 = csr_embedding_bag_sharded(bt, indices, offsets, 13, None,
                                         backend=backend)
        got2 = csr_embedding_bag_sharded(bt, indices, offsets[:13], 13, None,
                                         backend=backend)
        check(f"csr_sharded_fallback_{backend}",
              np.allclose(got1, want, atol=1e-5)
              and np.allclose(got2, want, atol=1e-5))


def check_migration_sharded():
    """shard_map migration (local permutation + psum row exchange) is
    bit-identical to a fresh pack of the same rows under the new plan —
    on BOTH exchange shapes: 'compact' ((n_moved, D) psum) and 'full'
    (packed-size psum, the parity baseline)."""
    from repro.workload import migrate_table
    rng = np.random.default_rng(13)
    V, D, banks = 96, 8, 2
    table = rng.standard_normal((V, D)).astype(np.float32)
    cap = (V // banks) + 16
    plan_a = non_uniform_partition(rng.random(V) + 0.1, banks,
                                   capacity_rows=cap)
    plan_b = non_uniform_partition(np.roll(rng.random(V) + 0.1, 31), banks,
                                   capacity_rows=cap)
    from repro.workload.migrate import permute_packed_rows
    import dataclasses
    t_a = pack_table(table, plan_a)
    t_a = dataclasses.replace(
        t_a,
        packed=permute_packed_rows(
            jnp.asarray(table),
            np.arange(V, dtype=np.int32),
            (plan_a.bank_of_row.astype(np.int64) * cap
             + plan_a.slot_of_row).astype(np.int32),
            banks * cap),
        rows_per_bank=cap)
    mesh = mesh42()
    dist = DistCtx(mesh=mesh, dp_axes=("data",))
    fresh = np.zeros((banks * cap, D), np.float32)
    fresh[plan_b.bank_of_row.astype(np.int64) * cap + plan_b.slot_of_row] \
        = table
    for exchange in ("compact", "full"):
        t_mig = migrate_table(t_a, plan_b, dist, rows_per_bank=cap,
                              exchange=exchange)
        check(f"migration_sharded_bitexact_{exchange}",
              (np.asarray(t_mig.packed) == fresh).all())
    # no-move replan: the compact path drops the collective entirely and
    # must still reproduce the (identical) layout bit-for-bit
    t_same = migrate_table(t_a, plan_a, dist, rows_per_bank=cap)
    check("migration_sharded_nomove",
          (np.asarray(t_same.packed) == np.asarray(t_a.packed)).all())


def check_cache_swap_sharded():
    """Live cache-path swap ON THE MESH: shard_map-migrated EMT + re-summed
    fixed-capacity GRACE table serve bit-identically (via the fused
    cache+residual lookup with its psum combine) to a from-scratch
    single-device rebuild at the same plan — the serve-side contract of
    launch/serve.py --adaptive --partition cache_aware."""
    import dataclasses as dc
    from repro.core.cache_runtime import (build_cache_table_fixed,
                                          cap_cache_plan, entry_banks)
    from repro.core.embedding import banked_cache_residual_bag
    from repro.core.grace import mine_cooccurrence
    from repro.workload import migrate_table, unpacked_rows
    from repro.workload.migrate import permute_packed_rows

    rng = np.random.default_rng(29)
    V, D, banks, cap, crpb = 96, 8, 2, (96 // 2) + 12, 8
    table = rng.standard_normal((V, D)).astype(np.float32)
    plan_a = non_uniform_partition(rng.random(V) + 0.1, banks,
                                   capacity_rows=cap)
    plan_b = non_uniform_partition(np.roll(rng.random(V) + 0.1, 31), banks,
                                   capacity_rows=cap)
    t_a = pack_table(table, plan_a)
    t_a = dc.replace(
        t_a,
        packed=permute_packed_rows(
            jnp.asarray(table), np.arange(V, dtype=np.int32),
            (plan_a.bank_of_row.astype(np.int64) * cap
             + plan_a.slot_of_row).astype(np.int32), banks * cap),
        rows_per_bank=cap)
    mesh = mesh42()
    dist = DistCtx(mesh=mesh, dp_axes=("data",))

    # the swap, sharded: migrate the EMT on the mesh, re-sum the cache side
    t_mig = migrate_table(t_a, plan_b, dist, rows_per_bank=cap)
    bags = [rng.choice(24, rng.integers(2, 7)) for _ in range(300)]
    cp = mine_cooccurrence(bags, top_items=48, max_groups=16, min_support=2)
    fcp = cap_cache_plan(cp, entry_banks(cp, plan_b.bank_of_row, None),
                         banks, crpb)
    ct = build_cache_table_fixed(unpacked_rows(t_mig), fcp, dtype=np.float32)

    # from-scratch single-device rebuild at the same plan
    t_fresh = dc.replace(
        pack_table(table, plan_b),
        packed=permute_packed_rows(
            jnp.asarray(table), np.arange(V, dtype=np.int32),
            (plan_b.bank_of_row.astype(np.int64) * cap
             + plan_b.slot_of_row).astype(np.int32), banks * cap),
        rows_per_bank=cap)
    ct_fresh = build_cache_table_fixed(table, fcp, dtype=np.float32)
    check("cache_swap_sharded_tables",
          (np.asarray(t_mig.packed) == np.asarray(t_fresh.packed)).all()
          and (np.asarray(ct.packed) == np.asarray(ct_fresh.packed)).all())

    ci = jnp.asarray(rng.integers(-1, fcp.n_entries or 1, (8, 3)), jnp.int32)
    ri = jnp.asarray(rng.integers(-1, V, (8, 6)), jnp.int32)
    fused = jax.jit(lambda t, c: banked_cache_residual_bag(
        t, c, ci, ri, dist, backend="jnp"))
    got = fused(t_mig, ct)
    # swapped vs fresh through the SAME sharded serve step: bit-identical
    # (the tables are; psum order is fixed). vs the single-device reference:
    # numerically equal (the psum's combine order differs in the last ulp).
    check("cache_swap_sharded_serve_bitexact",
          (np.asarray(got) == np.asarray(fused(t_fresh, ct_fresh))).all())
    want = banked_cache_residual_bag(t_fresh, ct_fresh, ci, ri, None,
                                     backend="jnp")
    check("cache_swap_sharded_serve_vs_local",
          np.allclose(got, want, atol=1e-5))


def check_pallas_backward_sharded():
    """The sorted-run Pallas scatter backward INSIDE the shard_map matches
    the XLA scatter fallback and the local jnp gradient, on all three
    custom_vjp paths (rectangular multi-field, fused cache+residual, CSR).
    This is the config that exposed the argsort-consumption miscompile the
    kernels' derived-operand prep works around."""
    import dataclasses
    from repro.core.embedding import (banked_cache_residual_bag,
                                      csr_embedding_bag)
    from repro.core.partitioning import uniform_partition
    rng = np.random.default_rng(23)
    V, D, banks = 64, 16, 2
    table = rng.standard_normal((V, D)).astype(np.float32)
    plan = non_uniform_partition(rng.random(V) + 0.1, banks)
    bt = pack_table(table, plan)
    fo = jnp.array([0, 20, 40], jnp.int32)
    idx = jnp.array(rng.integers(-1, 20, (8, 3, 5)), jnp.int32)
    mesh = mesh42()
    dist = DistCtx(mesh=mesh, dp_axes=("data",))

    def loss(packed, bwd, d):
        t2 = dataclasses.replace(bt, packed=packed)
        return (banked_embedding_bag(t2, idx, d, backend="pallas",
                                     bwd_backend=bwd,
                                     field_offsets=fo) ** 2).sum()

    gl = jax.grad(lambda p: loss(p, "jnp", None))(bt.packed)
    gp = jax.jit(jax.grad(lambda p: loss(p, "pallas", dist)))(bt.packed)
    gs = jax.jit(jax.grad(lambda p: loss(p, "jnp", dist)))(bt.packed)
    check("pallas_bwd_sharded_rect",
          np.allclose(gp, gl, atol=1e-4) and np.allclose(gp, gs, atol=1e-4))

    nc = 24
    ctab = rng.standard_normal((nc, D)).astype(np.float32)
    cbt = pack_table(ctab, uniform_partition(nc, banks))
    ci = jnp.asarray(rng.integers(-1, nc, (8, 3, 4)), jnp.int32)
    ri = jnp.asarray(rng.integers(-1, V, (8, 3, 6)), jnp.int32)

    def loss_c(ep, cp, bwd, d):
        t2 = dataclasses.replace(bt, packed=ep)
        c2 = dataclasses.replace(cbt, packed=cp)
        return (banked_cache_residual_bag(t2, c2, ci, ri, d,
                                          backend="pallas",
                                          bwd_backend=bwd) ** 2).sum()

    ge_p, gc_p = jax.jit(jax.grad(
        lambda e, c: loss_c(e, c, "pallas", dist),
        argnums=(0, 1)))(bt.packed, cbt.packed)
    ge_l, gc_l = jax.grad(
        lambda e, c: loss_c(e, c, "jnp", None),
        argnums=(0, 1))(bt.packed, cbt.packed)
    check("pallas_bwd_sharded_cache",
          np.allclose(ge_p, ge_l, atol=1e-4)
          and np.allclose(gc_p, gc_l, atol=1e-4))

    total, num_bags = 41, 7
    indices = jnp.asarray(rng.integers(-1, V, (total,)), jnp.int32)
    cuts = np.sort(rng.choice(np.arange(1, total), num_bags - 1,
                              replace=False))
    offsets = jnp.asarray(np.concatenate([[0], cuts]), jnp.int32)

    def loss_r(packed, bwd, d):
        t2 = dataclasses.replace(bt, packed=packed)
        return (csr_embedding_bag(t2, indices, offsets, num_bags, d,
                                  backend="pallas",
                                  bwd_backend=bwd) ** 2).sum()

    g_p = jax.jit(jax.grad(lambda p: loss_r(p, "pallas", dist)))(bt.packed)
    g_l = jax.grad(lambda p: loss_r(p, "jnp", None))(bt.packed)
    check("pallas_bwd_sharded_csr", np.allclose(g_p, g_l, atol=1e-4))


def check_tiered_lookup_sharded():
    """Tiered-precision lookup (repro.quant): shard_map stage 2 with
    in-kernel dequant matches the local path on both backends, and the
    straight-through gradient onto the fp master table matches the local
    full-precision gradient."""
    from repro.core.embedding import tiered_embedding_bag
    from repro.quant import QuantSpec, assign_tiers, build_tiered_table

    rng = np.random.default_rng(6)
    V, D, banks = 200, 16, 2
    table = (rng.standard_normal((V, D)) * 0.01).astype(np.float32)
    freq = rng.random(V) + 0.1
    plan = non_uniform_partition(freq, banks)
    bt = pack_table(table, plan)
    tiers = assign_tiers(freq, QuantSpec(byte_budget=12.0, min_hot_rows=4),
                         D).tier_of_row
    tt = build_tiered_table(bt, tiers)
    idx = jnp.array(rng.integers(-1, V, (8, 2, 5)), jnp.int32)
    mesh = mesh42()
    dist = DistCtx(mesh=mesh, dp_axes=("data",))
    loc = tiered_embedding_bag(bt.packed, tt, idx, None, backend="jnp")
    for be in ("jnp", "pallas"):
        sh = tiered_embedding_bag(bt.packed, tt, idx, dist, backend=be)
        check(f"tiered_lookup_sharded_{be}",
              np.allclose(np.asarray(sh), np.asarray(loc), atol=1e-6))
    g_loc = jax.grad(lambda p: tiered_embedding_bag(
        p, tt, idx, None, backend="jnp").sum())(bt.packed)
    g_sh = jax.grad(lambda p: tiered_embedding_bag(
        p, tt, idx, dist, backend="pallas").sum())(bt.packed)
    check("tiered_st_grads_sharded",
          np.allclose(np.asarray(g_sh), np.asarray(g_loc), atol=1e-6))


def check_degraded_serve_through_failure():
    """Fault-tolerant serving ON THE MESH: a bank dies mid-stream and the
    sharded lookup (a) stays bit-identical to the healthy path for requests
    not touching the dead bank, (b) zero-fills exactly the dead-bank rows
    (== the healthy path with those ids masked out), then (c) after the
    recovery replan + sharded migration, bit-matches a fresh pack — the
    serve-side contract of launch/serve.py --inject-bank-failure."""
    import dataclasses as dc
    from repro.launch.mesh import make_mesh
    from repro.core.embedding import degraded_row_counts
    from repro.workload import migrate_table
    from repro.workload.migrate import permute_packed_rows

    rng = np.random.default_rng(31)
    V, D, banks = 256, 8, 8
    cap = 40                         # 1.25x slack: one death is absorbable
    table = rng.standard_normal((V, D)).astype(np.float32)
    freq = rng.random(V) + 0.1
    plan = non_uniform_partition(freq, banks, capacity_rows=cap)
    t = dc.replace(
        pack_table(table, plan),
        packed=permute_packed_rows(
            jnp.asarray(table), np.arange(V, dtype=np.int32),
            (plan.bank_of_row.astype(np.int64) * cap
             + plan.slot_of_row).astype(np.int32), banks * cap),
        rows_per_bank=cap)
    mesh = make_mesh((1, banks), ("data", "model"))
    dist = DistCtx(mesh=mesh, dp_axes=("data",))
    idx = jnp.asarray(rng.integers(-1, V, (8, 6)), jnp.int32)
    all_live = jnp.ones(banks, dtype=bool)

    # healthy: the mask argument is a no-op bit-for-bit
    healthy = banked_embedding_bag(t, idx, dist)
    with_mask = banked_embedding_bag(t, idx, dist, bank_live=all_live)
    check("degraded_serve_healthy_mask_noop",
          (np.asarray(healthy) == np.asarray(with_mask)).all())

    # kill the most-loaded bank: degraded == healthy with dead ids masked
    dead = int(np.argmax(plan.load_per_bank))
    live = np.ones(banks, dtype=bool)
    live[dead] = False
    got = banked_embedding_bag(t, idx, dist, bank_live=jnp.asarray(live))
    idx_np = np.asarray(idx)
    on_dead = (idx_np >= 0) \
        & (plan.bank_of_row[np.where(idx_np >= 0, idx_np, 0)] == dead)
    masked = jnp.asarray(np.where(on_dead, -1, idx_np))
    want = banked_embedding_bag(t, masked, dist)
    check("degraded_serve_bounded",
          (np.asarray(got) == np.asarray(want)).all() and on_dead.any())
    counts = np.asarray(degraded_row_counts(t.remap_bank,
                                            jnp.asarray(live), idx))
    check("degraded_serve_counts_confined",
          (counts == on_dead.sum(axis=-1)).all())

    # recovery: replan off the dead bank, migrate ON THE MESH, bit-match a
    # fresh pack; the recovered table serves clean (zero degraded reads)
    plan2 = non_uniform_partition(freq, banks, capacity_rows=cap,
                                  bank_capacity_rows=np.where(live, cap, 0))
    t2 = migrate_table(t, plan2, dist, rows_per_bank=cap)
    fresh = np.zeros((banks * cap, D), np.float32)
    fresh[plan2.bank_of_row.astype(np.int64) * cap + plan2.slot_of_row] \
        = table
    check("degraded_recovery_migration_bitexact",
          (np.asarray(t2.packed) == fresh).all()
          and (np.asarray(t2.remap_bank) != dead).all())
    recovered = banked_embedding_bag(t2, idx, dist,
                                     bank_live=jnp.asarray(live))
    counts2 = np.asarray(degraded_row_counts(t2.remap_bank,
                                             jnp.asarray(live), idx))
    check("degraded_recovery_serves_clean",
          (counts2 == 0).all()
          and np.allclose(np.asarray(recovered), np.asarray(healthy),
                          atol=1e-6))


def check_lm_gspmd_matches_local():
    from repro.configs import get_arch
    from repro.models import transformer as T
    cfg = get_arch("smollm-135m").reduced
    params = T.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab)
    labels = jnp.roll(toks, -1, 1)
    mesh = mesh42()
    dist = DistCtx(mesh=mesh, dp_axes=("data",))
    from repro.dist.sharding import lm_param_shardings
    sh = lm_param_shardings(dist, params)
    params_d = jax.device_put(params, sh)
    loss_d = jax.jit(lambda p, t, l: T.lm_loss(cfg, p, t, l, dist))(
        params_d, toks, labels)
    loss_l = T.lm_loss(cfg, params, toks, labels, None)
    check("lm_gspmd_loss_matches", np.allclose(loss_d, loss_l, rtol=2e-3))


if __name__ == "__main__":
    check_banked_lookup_distributed()
    check_banked_lookup_grads()
    check_banked_pallas_backend()
    check_seqsharded_decode()
    check_gat_edge_sharded()
    check_dp_compressed_step()
    check_csr_sharded_lookup()
    check_migration_sharded()
    check_cache_swap_sharded()
    check_pallas_backward_sharded()
    check_tiered_lookup_sharded()
    check_degraded_serve_through_failure()
    check_lm_gspmd_matches_local()
    if FAILED:
        print("FAILED:", FAILED)
        sys.exit(1)
    print("ALL DIST CHECKS PASSED")
