"""Training CLI: ``python -m repro.launch.train --arch dlrm-rm2 [...]``.

Runs REDUCED configs end-to-end on local devices (this container is CPU) or
full configs on a real slice — same code path: config -> params -> partition
-> jit(train_step) -> loop with checkpointing, straggler watchdog, restart.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.configs import get_arch
from repro.data import synthetic as syn
from repro.dist.fault import StragglerWatchdog
from repro.obs.cli import add_obs_args, finalize_obs, setup_obs
from repro.train.train_step import TrainState, build_train_step, default_optimizer


def make_batch_fn(spec, cfg):
    fam = spec.family
    if fam == "lm":
        return lambda batch, seed, step: syn.lm_batch(
            batch, 64, cfg.vocab, seed=seed, step=step)
    if fam == "dlrm":
        return lambda batch, seed, step: syn.dlrm_batch(
            cfg.vocab_sizes, cfg.n_dense, batch, seed=seed, step=step,
            multi_hot=cfg.multi_hot)
    if fam == "din":
        return lambda batch, seed, step: syn.din_batch(
            cfg.n_items, cfg.n_cates, cfg.seq_len, batch, seed=seed,
            step=step)
    if fam == "bert4rec":
        return lambda batch, seed, step: syn.bert4rec_batch(
            cfg.n_items, cfg.seq_len, batch, seed=seed, step=step)
    if fam == "xdeepfm":
        return lambda batch, seed, step: syn.xdeepfm_batch(
            cfg.vocab_sizes, batch, seed=seed, step=step)
    raise ValueError(f"use examples/ for family {fam}")


def build_loss(spec, cfg, statics, backend: str | None = None,
               bwd_backend: str | None = None):
    """Family loss + the kwargs train_step should bind at the jit boundary
    (the dlrm embedding backend pair; other families take none)."""
    fam = spec.family
    if fam == "lm":
        from repro.models import transformer as T
        return (lambda p, b, **kw: T.lm_loss(cfg, p, b["tokens"],
                                             b["labels"])), {}
    mod = __import__(f"repro.models.{fam}", fromlist=["loss_fn"])
    kw = {}
    if fam == "dlrm":
        if backend is not None:
            kw["backend"] = backend
        if bwd_backend is not None:
            kw["bwd_backend"] = bwd_backend
    return (lambda p, b, **k: mod.loss_fn(cfg, p, statics, b, **k)), kw


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--emb-lr", type=float, default=1e-2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true",
                    help="full config (needs a real accelerator slice)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "jnp", "pallas", "tuned"),
                    help="embedding stage-2 backend (dlrm). 'pallas' keeps "
                         "the WHOLE embedding step near memory: fused "
                         "lookup kernel forward, sorted-run scatter kernel "
                         "backward. 'auto' resolves to 'tuned': per-shape "
                         "decisions from the committed TUNE_dispatch.json "
                         "autotuner cache, old auto rule on a miss")
    ap.add_argument("--bwd-backend", default="auto",
                    choices=("auto", "jnp", "pallas"),
                    help="override the gradient scatter only ('auto' "
                         "follows --backend; 'jnp' = XLA scatter fallback "
                         "under a pallas forward, the parity baseline)")
    ap.add_argument("--adaptive", action="store_true",
                    help="telemetry + drift-triggered repartitioning of the "
                         "banked table during training (dlrm only); the "
                         "row-wise Adagrad state migrates with its rows")
    ap.add_argument("--banks", type=int, default=8,
                    help="bank count for the adaptive partition")
    ap.add_argument("--replan-every", type=int, default=25,
                    help="steps between drift checks (--adaptive)")
    ap.add_argument("--capacity-slack", type=float, default=0.25,
                    help="per-bank row headroom over vocab/banks")
    ap.add_argument("--partition", default="non_uniform",
                    choices=("non_uniform", "cache_aware"),
                    help="adaptive replanner (--adaptive): plain banked "
                         "(§3.2, remaps re-jitted on migration) or the "
                         "fused GRACE cache+residual TRAIN path (§3.3): "
                         "remaps + cache table ride the step as jit "
                         "ARGUMENTS, so migrations and cache refreshes "
                         "swap through the VersionedCacheRewriter with "
                         "ZERO re-jits")
    ap.add_argument("--cache-entries", type=int, default=128,
                    help="TOTAL cache-entry capacity across banks "
                         "(cache_aware; fixed for the life of the run)")
    ap.add_argument("--cache-refresh-every", type=int, default=25,
                    help="steps between partial-sum refreshes: trained EMT "
                         "rows drift away from their cached sums, so the "
                         "entries are re-summed from CURRENT values and "
                         "published as a new rewriter version")
    add_obs_args(ap)
    args = ap.parse_args()
    if args.backend == "auto":
        args.backend = "tuned"   # auto now means: consult the dispatch cache
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    spec = get_arch(args.arch)
    cfg = spec.config if args.full else spec.reduced
    key = jax.random.key(args.seed)

    if args.adaptive and args.partition == "cache_aware":
        assert spec.family == "dlrm", "--adaptive drives the banked super-table"
        return _main_train_cached(args, spec, cfg, key)

    tracer, reg, writer = setup_obs(args, label=f"train:{args.arch}")
    m_step_ms = reg.histogram("train.step_ms", "jitted train-step wall time")
    m_migrations = reg.counter("train.migrations_total",
                               "drift-triggered table migrations")
    statics = None
    replanner = None
    cap = None
    if args.adaptive:
        assert spec.family == "dlrm", "--adaptive drives the banked super-table"
        from repro.core.partitioning import non_uniform_partition
        from repro.workload import (ReplanConfig, Replanner,
                                    rows_from_sparse)
        V = cfg.total_vocab
        cap = int(np.ceil(V / args.banks) * (1.0 + args.capacity_slack))
        plan = non_uniform_partition(np.ones(V), args.banks,
                                     capacity_rows=cap)
        replanner = Replanner(
            ReplanConfig.for_vocab(V, args.banks, capacity_rows=cap,
                                   check_every=args.replan_every),
            V, init_freq=np.ones(V), metrics=reg)
    if spec.family == "lm":
        from repro.models import transformer as T
        params = T.init_params(cfg, key)
    elif args.adaptive:
        mod = __import__(f"repro.models.{spec.family}",
                         fromlist=["init_params"])
        params, statics = mod.init_params(cfg, key, plan=plan,
                                          rows_per_bank=cap)
    else:
        mod = __import__(f"repro.models.{spec.family}",
                         fromlist=["init_params"])
        params, statics = mod.init_params(cfg, key)

    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={args.arch} family={spec.family} params={n_params:,}")

    opt = default_optimizer(lr=args.lr, emb_lr=args.emb_lr)
    loss_fn, loss_kw = build_loss(spec, cfg, statics, backend=args.backend,
                                  bwd_backend=args.bwd_backend)
    step_fn = jax.jit(build_train_step(loss_fn, opt,
                                       compress_grads=args.compress_grads,
                                       loss_kwargs=loss_kw))
    state = TrainState.create(params, opt, compress=args.compress_grads)

    start = 0
    ck = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ck and latest_step(args.ckpt_dir) is not None:
        state, start = restore_checkpoint(args.ckpt_dir, state)
        print(f"restored step {start}")
        # --adaptive: the checkpointed emb_packed follows whatever plan was
        # live at save time, NOT the deterministic initial plan — restore the
        # remap vectors saved FOR THIS STEP (per-step files: the restored
        # checkpoint may not be the newest save, e.g. a crash mid-write) or
        # every lookup would silently gather the wrong rows
        if replanner is not None:
            remaps = _load_remaps(args.ckpt_dir, start)
            if remaps is not None:
                statics["remap_bank"] = jnp.asarray(remaps["remap_bank"])
                statics["remap_slot"] = jnp.asarray(remaps["remap_slot"])

    batch_fn = make_batch_fn(spec, cfg)
    wd = StragglerWatchdog(metrics=reg)
    t_begin = time.time()
    n_migrations = 0
    field_offs = np.asarray(statics["field_offsets"]) if replanner else None
    traffic = None
    bank_of_row = None
    if replanner is not None:
        # train-side bank-traffic attribution: the step is re-jitted on
        # migration (remaps are closure constants here), so the recount runs
        # host-side on the SAME rows telemetry observes — the numpy twin of
        # the serve path's in-jit counters, landing in the same obs.bank_*
        # series
        from repro.obs.traffic import TrafficAccumulator, host_bank_read_counts
        row_nbytes = (state.params["emb_packed"].shape[-1]
                      * np.dtype(np.float32).itemsize)
        traffic = TrafficAccumulator(reg, args.banks, row_nbytes=row_nbytes)
        bank_of_row = np.asarray(statics["remap_bank"])  # restore-aware
    for step in range(start, args.steps):
        with tracer.span("rewrite", step=step):
            b = batch_fn(args.batch, args.seed, step)
            if replanner is not None:
                rows = rows_from_sparse(b["sparse"], field_offs)
                replanner.observe_rows(rows)
                traffic.update(
                    host_bank_read_counts(bank_of_row, rows, args.banks))
            b = {k: jnp.asarray(v) for k, v in b.items()}
        t0 = time.time()
        with tracer.span("device_step", step=step):
            state, metrics = step_fn(state, b)
            loss = float(metrics["loss"])
        m_step_ms.observe((time.time() - t0) * 1e3)
        wd.observe(step, time.time() - t0)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {loss:.4f} "
                  f"({(time.time() - t0) * 1e3:.0f} ms)")
        if replanner is not None:
            update = replanner.end_batch()
            if update is not None:
                # migrate table rows + their row-wise Adagrad history in one
                # pass, swap the remap vectors, rebuild the jitted step (the
                # remaps are closure constants on the train path)
                from repro.core.embedding import BankedTable
                from repro.workload import migrate_packed_leaves
                with tracer.span("migrate", step=step):
                    old_t = BankedTable(packed=state.params["emb_packed"],
                                        remap_bank=statics["remap_bank"],
                                        remap_slot=statics["remap_slot"],
                                        n_banks=args.banks,
                                        rows_per_bank=cap)
                    state = migrate_packed_leaves(state, old_t, update.plan,
                                                  rows_per_bank=cap)
                    statics["remap_bank"] = jnp.asarray(
                        update.plan.bank_of_row, jnp.int32)
                    statics["remap_slot"] = jnp.asarray(
                        update.plan.slot_of_row, jnp.int32)
                    loss_fn, loss_kw = build_loss(
                        spec, cfg, statics, backend=args.backend,
                        bwd_backend=args.bwd_backend)
                    step_fn = jax.jit(build_train_step(
                        loss_fn, opt, compress_grads=args.compress_grads,
                        loss_kwargs=loss_kw))
                n_migrations += 1
                m_migrations.inc()
                bank_of_row = update.plan.bank_of_row
                print(f"  [migrate @step {step}] {update.report} "
                      f"imbalance -> {update.plan.imbalance():.3f}")
        if writer is not None:
            writer.maybe_write(step + 1)
        if ck and (step + 1) % args.ckpt_every == 0:
            if replanner is not None:
                _save_remaps(args.ckpt_dir, statics, step + 1)
            ck.save(step + 1, state)
    if ck:
        if replanner is not None:
            _save_remaps(args.ckpt_dir, statics, args.steps)
        ck.save(args.steps, state)
        ck.join()
    extra = f"; migrations={n_migrations}" if replanner is not None else ""
    if traffic is not None and traffic.batches:
        reads = np.asarray(traffic.reads.values)
        extra += (f"; bank traffic: {int(reads.sum())} reads, "
                  f"max-bank share {reads.max() / max(reads.sum(), 1):.3f} "
                  f"over {traffic.batches} batches")
    print(f"done in {time.time() - t_begin:.1f}s; stragglers={wd.events}"
          + extra)
    finalize_obs(args, tracer, reg, writer, prefix="train")


def _main_train_cached(args, spec, cfg, key) -> None:
    """Cache-aware TRAINING under the adaptive runtime (the PR-4 open item,
    closed): the fused cache+residual loss takes the EMT remap vectors and
    the GRACE cache table as step ARGUMENTS, so a drift migration — and the
    periodic partial-sum refresh that training makes necessary — both swap
    through the ``VersionedCacheRewriter`` between steps, against ONE jitted
    executable. The old path rebuilt the cache table and re-jitted the step
    on every refresh cadence; now a refresh is ``runtime.refresh_cache()``:
    re-sum the surviving entries from the CURRENT trained row values,
    publish as version v+1, done. The row-wise Adagrad accumulator still
    migrates with its rows (``migrate_packed_leaves``) before the runtime
    adopts the migrated table (``apply_migrated``).
    """
    from repro.core.embedding import BankedTable
    from repro.core.partitioning import non_uniform_partition
    from repro.workload import (AdaptiveEmbeddingRuntime, ReplanConfig,
                                migrate_packed_leaves)

    mod = __import__(f"repro.models.{spec.family}", fromlist=["loss_fn"])
    mh = cfg.multi_hot
    assert mh >= 2, ("--partition cache_aware needs multi-hot bags "
                     "(try --arch updlrm-paper); GRACE partial sums fuse "
                     ">=2 lookups of one bag")
    banks = args.banks
    V = cfg.total_vocab
    cap = int(np.ceil(V / banks) * (1.0 + args.capacity_slack))
    crpb = max(1, -(-args.cache_entries // banks))
    plan = non_uniform_partition(np.ones(V), banks, capacity_rows=cap)
    params, statics = mod.init_params(cfg, key, plan=plan, rows_per_bank=cap)
    offs = np.asarray(statics["field_offsets"])

    table = BankedTable(packed=params["emb_packed"],
                        remap_bank=statics["remap_bank"],
                        remap_slot=statics["remap_slot"],
                        n_banks=banks, rows_per_bank=cap)
    rcfg = ReplanConfig.for_vocab(V, banks, capacity_rows=cap,
                                  check_every=args.replan_every,
                                  partitioner="cache_aware",
                                  cache_rows_per_bank=crpb,
                                  mine_min_support=2,
                                  telemetry_decay=0.8,
                                  telemetry_decay_every=4096)
    tracer, reg, writer = setup_obs(args, label=f"train-cached:{args.arch}")
    m_step_ms = reg.histogram("train.step_ms", "jitted train-step wall time")
    m_migrations = reg.counter("train.migrations_total",
                               "drift-triggered table migrations")
    m_refreshes = reg.counter("train.cache_refreshes_total",
                              "periodic partial-sum re-sums (staleness)")
    runtime = AdaptiveEmbeddingRuntime(
        table, plan, rcfg, init_freq=np.ones(V),
        max_cache_per_bag=max(2, mh // 4), max_residual_per_bag=mh,
        tracer=tracer, metrics=reg)

    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"arch={args.arch} family={spec.family} params={n_params:,} "
          f"(cache-aware train, {banks * crpb} entry capacity)")

    kw = {}
    if args.backend is not None:
        kw["backend"] = args.backend
    if args.bwd_backend is not None:
        kw["bwd_backend"] = args.bwd_backend

    def loss_cached(p, b, **k):
        batch_c = {"dense": b["dense"], "cache_idx": b["cache_idx"],
                   "residual_idx": b["residual_idx"]}
        logits = mod.forward_cached(cfg, p, statics, b["cache_table"],
                                    batch_c, remap_bank=b["remap_bank"],
                                    remap_slot=b["remap_slot"], **k)
        return mod.bce_loss(logits, b["label"])

    opt = default_optimizer(lr=args.lr, emb_lr=args.emb_lr)
    step_fn = jax.jit(build_train_step(loss_cached, opt,
                                       compress_grads=args.compress_grads,
                                       loss_kwargs=kw))
    state = TrainState.create(params, opt, compress=args.compress_grads)

    batch_fn = make_batch_fn(spec, cfg)
    wd = StragglerWatchdog(metrics=reg)
    t_begin = time.time()
    n_migrations = n_refreshes = 0
    # bank-traffic attribution on the fused train path: the numpy twin of
    # the serve step's in-jit cache+residual counter, fed from the SAME
    # rewritten bags the step consumes — one obs.bank_* accounting path
    # across serve and train
    from repro.obs.traffic import (TrafficAccumulator,
                                   host_cached_bank_read_counts)
    traffic = TrafficAccumulator(
        reg, banks,
        row_nbytes=int(params["emb_packed"].shape[-1]) * 4)
    for step in range(args.steps):
        with tracer.span("rewrite", step=step):
            b = batch_fn(args.batch, args.seed, step)
            sp = np.asarray(b["sparse"])                   # (B, F, L)
            union = np.where(sp >= 0, sp + offs[None, :, None], -1)
            runtime.observe_bags(
                [bag[bag >= 0]
                 for bag in union.reshape(-1, union.shape[-1])])
            rb = runtime.rewrite(union)
            # everything a swap replaces is a step ARGUMENT; the batch
            # resolves against the cache-table version it was rewritten for
            batch = {"dense": jnp.asarray(b["dense"]),
                     "label": jnp.asarray(b["label"]),
                     "cache_idx": jnp.asarray(rb.cache_idx),
                     "residual_idx": jnp.asarray(rb.residual_idx),
                     "remap_bank": runtime.table.remap_bank,
                     "remap_slot": runtime.table.remap_slot,
                     "cache_table": runtime.cache_table_for(rb.version)}
            traffic.update(host_cached_bank_read_counts(
                np.asarray(batch["cache_table"].remap_bank), rb.cache_idx,
                np.asarray(runtime.table.remap_bank), rb.residual_idx,
                banks))
        t0 = time.time()
        with tracer.span("device_step", step=step):
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
        m_step_ms.observe((time.time() - t0) * 1e3)
        wd.observe(step, time.time() - t0)
        # the trained table: rebind the runtime's view to the new params so
        # replans/refreshes re-sum from CURRENT values
        runtime.table = BankedTable(packed=state.params["emb_packed"],
                                    remap_bank=runtime.table.remap_bank,
                                    remap_slot=runtime.table.remap_slot,
                                    n_banks=banks, rows_per_bank=cap)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {loss:.4f} "
                  f"({(time.time() - t0) * 1e3:.0f} ms)")
        update = runtime.replanner.end_batch()
        if update is not None:
            # migrate params + row-wise Adagrad state in one pass, then the
            # runtime adopts the migrated table and swaps the cache lane
            # versioned — no step re-jit (remaps are arguments)
            with tracer.span("migrate", step=step):
                state = migrate_packed_leaves(state, runtime.table,
                                              update.plan, rows_per_bank=cap)
                new_table = BankedTable(
                    packed=state.params["emb_packed"],
                    remap_bank=jnp.asarray(update.plan.bank_of_row,
                                           jnp.int32),
                    remap_slot=jnp.asarray(update.plan.slot_of_row,
                                           jnp.int32),
                    n_banks=banks, rows_per_bank=cap)
            event = runtime.apply_migrated(update, new_table)
            n_migrations += 1
            m_migrations.inc()
            print(f"  [migrate @step {step}] {update.report} "
                  f"imbalance -> {update.plan.imbalance():.3f}  "
                  f"cache v{event.cache_version} "
                  f"entries {event.cache_entries}")
        elif (step + 1) % args.cache_refresh_every == 0:
            with tracer.span("cache_refresh", step=step):
                version = runtime.refresh_cache()
            n_refreshes += 1
            m_refreshes.inc()
            print(f"  [cache refresh @step {step}] re-summed "
                  f"{runtime.cache_plan.n_entries} entries -> v{version}")
        if writer is not None:
            writer.maybe_write(step + 1)
    executables = step_fn._cache_size()
    reads = np.asarray(traffic.reads.values)
    print(f"done in {time.time() - t_begin:.1f}s; stragglers={wd.events}; "
          f"migrations={n_migrations} refreshes={n_refreshes}; "
          f"bank traffic: {int(reads.sum())} reads, max-bank share "
          f"{reads.max() / max(reads.sum(), 1):.3f}; "
          f"{executables} step executable(s) "
          f"({'ZERO re-jits' if executables == 1 else 'RE-JITTED'})")
    reg.gauge("jax.step_executables").set(executables)
    finalize_obs(args, tracer, reg, writer, prefix="train")


def _remaps_path(ckpt_dir: str, step: int) -> str:
    import os
    return os.path.join(ckpt_dir, f"adaptive_remaps_{step}.npz")


def _save_remaps(ckpt_dir: str, statics: dict, step: int) -> None:
    """Persist the LIVE plan's remap vectors for THIS checkpoint step — the
    packed table layout and its remaps must restore as a pair, and the
    restored step may be older than the newest remaps (checkpoints are
    written asynchronously and pruned; restore picks the newest COMPLETE
    one). Written synchronously BEFORE ck.save so a crash can only orphan a
    remaps file, never a checkpoint."""
    import os
    os.makedirs(ckpt_dir, exist_ok=True)
    np.savez(_remaps_path(ckpt_dir, step),
             remap_bank=np.asarray(statics["remap_bank"]),
             remap_slot=np.asarray(statics["remap_slot"]))


def _load_remaps(ckpt_dir: str, step: int):
    import os
    p = _remaps_path(ckpt_dir, step)
    if not os.path.exists(p):
        return None     # checkpoint predates --adaptive: initial plan holds
    with np.load(p) as z:
        return {"remap_bank": z["remap_bank"], "remap_slot": z["remap_slot"]}


if __name__ == "__main__":
    main()
