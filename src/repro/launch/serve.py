"""Serving CLI: ``python -m repro.launch.serve --arch dlrm-rm2``.

Simulates the paper's online-inference setup with the MicroBatcher: a stream
of requests, cache-aware rewriting in the pre-process stage, jitted scoring,
p50/p99 latency report.

``--adaptive`` (dlrm only) turns on the repro.workload closed loop: requests
come from a DRIFTING Zipf stream, the MicroBatcher's observer tap feeds the
telemetry, and on detected drift the table is repartitioned and live-migrated
between micro-batches. The remap vectors are jit ARGUMENTS (not closure
constants) and the packed shape is pinned to a fixed per-bank capacity, so a
swap never recompiles the serve step.

``--adaptive --partition cache_aware`` serves the FUSED cache+residual path
(paper Fig. 7) under the same loop: every micro-batch is host-rewritten
against the current GRACE plan and version-tagged; a drifted replan re-mines
the co-occurrence groups, migrates the EMT, re-sums the cache table from the
migrated rows at a FIXED entry capacity, and swaps (rewrite plan, cache
table, remap vectors) atomically between micro-batches — batches in flight
across the swap resolve against the cache-table version they were rewritten
for. A compile-count probe (jax.monitoring + the jit cache size) asserts the
whole run used ONE serve executable, and the first swap is verified
bit-identical to tearing down and rebuilding the cache path from scratch
(``--min-swaps`` makes both checks a hard exit code for CI).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.obs.cli import add_obs_args as _add_obs_args
from repro.obs.cli import finalize_obs as _finalize_obs
from repro.obs.cli import setup_obs as _setup_obs
from repro.serve.serve_step import MicroBatcher, Request


def _projected_share(runtime) -> float:
    """Plan-time projected max-bank share of the INSTALLED plan on the
    recent telemetry window — the promise the SLO watchdog's divergence
    check holds the measured traffic against. Cache-aware lanes project
    through the bag-replay model (reads the cache absorbs count for the
    plan), everything else uses the row-share projection."""
    rp = runtime.replanner
    fcp = rp.current_cache_fixed
    if fcp is not None and rp._recent_bags:
        return rp.projected_max_share_cached(runtime.plan, fcp,
                                             list(rp._recent_bags))
    return rp.projected_max_share(runtime.plan, rp.telemetry.freq_vector())


class _TrafficSLO:
    """One serve loop's measured-traffic lane: the TrafficAccumulator
    (``obs.bank_reads`` / ``obs.bank_bytes`` / ``obs.bank_share``), the SLO
    watchdog, and the Chrome-trace counter tracks. Built unconditionally by
    every adaptive main so the metrics snapshot carries the whole ``obs.*``
    family whether or not any SLO check is armed (the CI metrics-schema
    gate keys on the names, not the values)."""

    def __init__(self, args, metrics, tracer, *, banks, dim, row_nbytes,
                 runtime=None):
        from repro.obs.slo import SLOConfig, SLOWatchdog, hot_bank_penalty
        from repro.obs.traffic import TrafficAccumulator
        self.tracer = tracer
        self.banks = banks
        self.acc = TrafficAccumulator(metrics, banks, row_nbytes=row_nbytes)
        self.penalties = 0

        def on_breach(kind, info):
            if runtime is None:
                return
            pen = hot_bank_penalty(info["window_reads"], banks)
            runtime.on_slo_breach(pen)
            self.penalties += 1
            print(f"  [slo breach @batch {info['batch']}] {kind}: "
                  f"{info['value']:.1f} > {info['threshold']:.1f} "
                  f"(hot bank {info['bank']}, penalty "
                  f"x{pen.max():.2f} -> replanner)")

        cfg = SLOConfig(p99_us=args.slo_p99_us, max_share=args.slo_max_share,
                        divergence=args.slo_divergence, window=args.slo_window)
        self.watchdog = SLOWatchdog(cfg, n_banks=banks, dim=dim,
                                    metrics=metrics, tracer=tracer,
                                    on_breach=on_breach)
        if runtime is not None:
            self.watchdog.set_projection(_projected_share(runtime))

    @property
    def breaches(self) -> int:
        return self.watchdog.breaches

    def on_swap(self, runtime) -> None:
        """Refresh the plan-time projection after a live swap."""
        self.watchdog.set_projection(_projected_share(runtime))

    def after_step(self, batch, reads, wall_us, batch_size, *, nbytes=None,
                   p99_ms=None):
        """Fold one batch's measured counts; feed the watchdog."""
        reads = np.asarray(reads)
        share = self.acc.update(reads, nbytes if nbytes is None
                                else np.asarray(nbytes))
        self.tracer.counter(
            "bank_reads", **{f"bank{i}": int(v) for i, v in enumerate(reads)})
        self.tracer.counter("serve_slo", max_bank_share=share,
                            **({} if p99_ms is None else {"p99_ms": p99_ms}))
        self.watchdog.observe(batch, wall_us=wall_us, reads=reads,
                              batch_size=batch_size)
        return share

    def check_contract(self, min_breaches: int) -> None:
        """The CI SLO contract: at least ``min_breaches`` detected AND the
        replanner actually received a penalty for each breach lane."""
        if min_breaches <= 0:
            return
        if self.breaches < min_breaches or self.penalties < 1:
            raise SystemExit(
                f"slo contract violated: breaches={self.breaches} "
                f"(need >= {min_breaches}), replanner penalties="
                f"{self.penalties} (need >= 1)")


class CompileProbe:
    """Counts XLA compilations via jax.monitoring — the zero-recompile
    assertion for live swaps (each jit compilation emits one
    '/jax/…compile…' event; cache hits emit none)."""

    def __init__(self, metrics=None):
        self.compiles = 0
        if metrics is None:
            from repro.obs import MetricRegistry
            metrics = MetricRegistry()
        self._m_compiles = metrics.counter("jax.compiles_total",
                                           "XLA compilations (monitoring)")
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **kw) -> None:
        if "compile" in name:
            self.compiles += 1
            self._m_compiles.inc()


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dlrm-rm2")
    ap.add_argument("--full", action="store_true",
                    help="serve the published config (needs a real "
                         "accelerator); default: the reduced smoke config")
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "jnp", "pallas", "tuned"),
                    help="embedding stage-2 backend (dlrm only). 'auto' "
                         "resolves to 'tuned': per-shape decisions from the "
                         "committed TUNE_dispatch.json autotuner cache, "
                         "falling back to the old auto rule on a cache miss")
    ap.add_argument("--adaptive", action="store_true",
                    help="online telemetry + drift-triggered repartitioning "
                         "with live table migration (dlrm only)")
    ap.add_argument("--partition", default="non_uniform",
                    choices=("non_uniform", "cache_aware"),
                    help="adaptive replanner: plain banked (§3.2) or the "
                         "fused GRACE cache+residual serve path (§3.3)")
    ap.add_argument("--banks", type=int, default=8,
                    help="bank count for the adaptive partition")
    ap.add_argument("--replan-every", type=int, default=8,
                    help="micro-batches between drift checks")
    ap.add_argument("--capacity-slack", type=float, default=0.25,
                    help="per-bank row headroom over vocab/banks")
    ap.add_argument("--cache-entries", type=int, default=128,
                    help="TOTAL cache-entry capacity across banks "
                         "(cache_aware; fixed for the life of the server)")
    ap.add_argument("--drift-rotate-every", type=int, default=512,
                    help="requests between hot-set rotations of the "
                         "synthetic drifting stream")
    ap.add_argument("--min-swaps", type=int, default=0,
                    help="exit nonzero unless at least this many live swaps "
                         "occurred AND the swap invariants (bit-parity with "
                         "a from-scratch rebuild, zero recompiles) held — "
                         "the CI serve-smoke contract")
    ap.add_argument("--replicate-k-max", type=int, default=1,
                    help="hot-row replication on the adaptive serve path "
                         "(dlrm --adaptive, non_uniform): give the "
                         "telemetry-chosen hottest rows up to this many "
                         "copies on distinct banks; an in-kernel per-bag "
                         "hash splits their traffic. 1 = off. Replans "
                         "re-pick the replicated set through the same "
                         "zero-recompile swap")
    ap.add_argument("--replicate-max-r", type=int, default=64,
                    help="cap on the number of replicated rows per plan "
                         "(bounds the extra-copy capacity cost; further "
                         "clamped so the copies always fit the fixed "
                         "per-bank capacity)")
    ap.add_argument("--quant", default="off", choices=("off", "int8", "int4"),
                    help="tiered-precision embedding storage (repro.quant) "
                         "on the adaptive serve path: hot head stays bf16, "
                         "the tail quantizes to int8 (or int8+packed-int4); "
                         "replans re-tier rows through the same zero-"
                         "recompile swap (dlrm --adaptive, non_uniform)")
    ap.add_argument("--quant-byte-budget", type=float, default=None,
                    help="target average STORED bytes per row (README.md "
                         "§byte budget); default: int8 tail (--quant int8) "
                         "or a mostly-int4 mix (--quant int4)")
    ap.add_argument("--quant-hot-rows", type=int, default=8,
                    help="hottest rows pinned to the full-precision tier")
    ap.add_argument("--hysteresis", type=float, default=0.0,
                    help="skip drifted replans whose candidate plan does "
                         "not beat the incumbent's projected max-bank share "
                         "by this relative margin (0 = replan on every "
                         "drifted check)")
    ap.add_argument("--inject-bank-failure", action="append", default=[],
                    metavar="BATCH:BANK[:STATE[:FACTOR]]",
                    help="fault-tolerant serving lane (dlrm --adaptive, "
                         "non_uniform): kill bank BANK at micro-batch BATCH "
                         "(state 'dead', the default), slow it (state "
                         "'degraded', FACTOR x), or revive it ('healthy'). "
                         "Repeatable. Serving continues through the failure "
                         "with bounded-degraded reads; recovery re-packs the "
                         "dead bank's rows onto survivors via the replan "
                         "lane")
    ap.add_argument("--straggler-factor", type=float, default=3.0,
                    help="StragglerWatchdog threshold: a micro-batch whose "
                         "modeled bank time exceeds this multiple of the "
                         "running median flags its slowest bank, feeding a "
                         "latency penalty into the planner's load model")
    ap.add_argument("--slo-p99-us", type=float, default=0.0,
                    help="SLO watchdog (dlrm --adaptive): breach when the "
                         "rolling-window p99 of measured device-step wall "
                         "time exceeds this budget (microseconds; 0 = check "
                         "off). Breaches mark the Chrome trace, bump "
                         "obs.slo_breaches_total, and push a hot-bank "
                         "penalty into the replanner")
    ap.add_argument("--slo-max-share", type=float, default=0.0,
                    help="SLO watchdog: breach when the window-mean MEASURED "
                         "max-bank read share exceeds this fraction "
                         "(0 = check off; 1/banks is perfect balance)")
    ap.add_argument("--slo-divergence", type=float, default=0.0,
                    help="SLO watchdog: breach when the realized modeled "
                         "latency (hwmodel priced at MEASURED bank shares) "
                         "exceeds the plan-time projection by this relative "
                         "margin (0 = check off)")
    ap.add_argument("--slo-window", type=int, default=16,
                    help="micro-batches per SLO evaluation window (also the "
                         "per-check cooldown after a breach fires)")
    ap.add_argument("--min-slo-breaches", type=int, default=0,
                    help="exit nonzero unless at least this many SLO "
                         "breaches were detected AND the replanner received "
                         "the hot-bank penalty — the CI measure->plan "
                         "feedback contract")
    ap.add_argument("--min-recoveries", type=int, default=0,
                    help="exit nonzero unless at least this many "
                         "bank-failure recoveries completed AND the fault "
                         "contracts held (degradation confined to dead-bank "
                         "rows, post-recovery bit-parity with a never-failed "
                         "run, one serve executable) — the CI "
                         "failure-injection contract")
    _add_obs_args(ap)
    args = ap.parse_args(argv)
    if args.backend == "auto":
        args.backend = "tuned"   # auto now means: consult the dispatch cache
    return args


def main(argv: list[str] | None = None) -> dict | None:
    """Run the CLI; the plain loop returns what it served (``serve_plain``)."""
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = parse_args(argv)
    spec = get_arch(args.arch)
    assert spec.family in ("dlrm", "din", "xdeepfm"), "recsys serving CLI"
    cfg = spec.config if args.full else spec.reduced
    mod = __import__(f"repro.models.{spec.family}", fromlist=["forward"])
    if args.adaptive:
        assert spec.family == "dlrm", "--adaptive drives the banked super-table"
        return _main_adaptive(args, spec, cfg, mod)
    return serve_plain(args, spec, cfg, mod)


def serve_plain(args, spec, cfg, mod) -> dict:
    """The plain serve loop: requests -> MicroBatcher -> compiled scoring.

    The step is compiled before the first request arrives, so compilation
    is set-up time and stays out of the request latencies. Returns what the
    loop served with: the ``lowered`` and compiled ``serve`` step, its
    ``params`` and ``tables`` arguments, the ``statics`` they came from,
    ``compile_s``, and the last ``batch`` with its ``scores``.
    """
    params, statics = mod.init_params(cfg, jax.random.key(args.seed))
    from repro.serve.serve_step import build_recsys_serve, serve_tables
    backend = args.backend if spec.family == "dlrm" else None
    tables = serve_tables(statics)

    rng = np.random.default_rng(args.seed)
    from repro.data import synthetic as syn
    if spec.family == "dlrm":
        proto = syn.dlrm_batch(cfg.vocab_sizes, cfg.n_dense, 1, seed=0,
                               step=0, multi_hot=cfg.multi_hot)
    elif spec.family == "din":
        proto = syn.din_batch(cfg.n_items, cfg.n_cates, cfg.seq_len, 1,
                              seed=0, step=0)
    else:
        proto = syn.xdeepfm_batch(cfg.vocab_sizes, 1, seed=0, step=0)
    proto.pop("label", None)
    pad = {k: v[0] for k, v in proto.items()}

    # the MicroBatcher's batch type: args.batch stacked device rows
    batch_type = {k: jax.ShapeDtypeStruct((args.batch, *np.shape(v)),
                                          jnp.asarray(v).dtype)
                  for k, v in pad.items()}
    t0 = time.perf_counter()
    lowered = jax.jit(build_recsys_serve(mod, cfg, statics, backend=backend)
                      ).lower(params, tables, batch_type)
    serve = lowered.compile()
    compile_s = time.perf_counter() - t0
    print(f"compiled serve step in {compile_s:.2f}s")

    tracer, metrics, writer = _setup_obs(args, label=f"serve:{args.arch}")
    mb = MicroBatcher(args.batch, pad, metrics=metrics)
    n_batches = 0
    last = {}

    def run_batch():
        nonlocal n_batches
        with tracer.span("rewrite"):
            reqs, feats_b = mb.next_batch()
        with tracer.span("device_step", batch=n_batches):
            scores = serve(params, tables, feats_b)
            jax.block_until_ready(scores)
        mb.complete(reqs)
        n_batches += 1
        last.update(batch=feats_b, scores=scores)
        if writer is not None:
            writer.maybe_write(n_batches)

    for rid in range(args.requests):
        feats = {k: v[0] for k, v in _one(spec, cfg, rng, rid).items()}
        mb.submit(Request(rid=rid, features=feats))
        if len(mb.queue) >= args.batch:
            run_batch()
    while mb.ready():
        run_batch()

    lat = sorted(mb.latencies)
    p50 = lat[len(lat) // 2] * 1e3
    print(f"served {len(lat)} requests  p50={p50:.2f}ms "
          f"p99={mb.p99() * 1e3:.2f}ms")
    _finalize_obs(args, tracer, metrics, writer, latencies=mb.latencies)
    return dict(lowered=lowered, serve=serve, params=params, tables=tables,
                statics=statics, compile_s=compile_s, **last)


def _main_adaptive(args, spec, cfg, mod) -> None:
    """Drifting traffic -> telemetry -> replan -> migrate -> swap, live."""
    from repro.core.embedding import BankedTable
    from repro.core.partitioning import non_uniform_partition
    from repro.workload import (AdaptiveEmbeddingRuntime, DriftConfig,
                                DriftingZipfTrace, ReplanConfig,
                                dlrm_drifting_batch, rows_from_sparse)

    if args.inject_bank_failure:
        assert args.partition == "non_uniform", (
            "--inject-bank-failure rides the non_uniform adaptive path "
            "(cache_aware recovery packing is a ROADMAP item)")
        assert args.quant == "off", ("--inject-bank-failure serves the "
                                     "full-precision path")
        assert args.replicate_k_max <= 1, (
            "--inject-bank-failure x --replicate-k-max in one run is a "
            "ROADMAP item; replica failover itself is covered by "
            "tests/test_replication.py")
        return _main_adaptive_fault(args, spec, cfg, mod)
    if args.replicate_k_max > 1:
        assert args.partition == "non_uniform", (
            "--replicate-k-max rides the non_uniform adaptive path "
            "(cache_aware entry placement has no replica axis)")
        assert args.quant == "off", (
            "--replicate-k-max serves the full-precision path; the "
            "dequant+replica-select kernel cross-product is a ROADMAP item")
        return _main_adaptive_replicated(args, spec, cfg, mod)
    if args.partition == "cache_aware":
        assert args.quant == "off", ("--quant rides the non_uniform adaptive "
                                     "path; the cache+residual tiered "
                                     "cross-product is a ROADMAP item")
        return _main_adaptive_cached(args, spec, cfg, mod)

    banks = args.banks
    V = cfg.total_vocab
    cap = int(np.ceil(V / banks) * (1.0 + args.capacity_slack))
    plan = non_uniform_partition(np.ones(V), banks, capacity_rows=cap)
    params, statics = mod.init_params(cfg, jax.random.key(args.seed),
                                      plan=plan, rows_per_bank=cap)
    offs = np.asarray(statics["field_offsets"])

    quant_on = args.quant != "off"
    qspec = None
    if quant_on:
        from repro.quant import QuantSpec
        budget = args.quant_byte_budget
        if budget is None and args.quant == "int4":
            # mostly-int4 mix: the packed width plus a little int8 headroom
            budget = cfg.embed_dim // 2 + 2.0
        qspec = QuantSpec(enable_int4=(args.quant == "int4"),
                          byte_budget=budget,
                          min_hot_rows=args.quant_hot_rows)
    tracer, metrics, writer = _setup_obs(
        args, label=f"serve-adaptive:{args.arch}:quant={args.quant}")
    probe = CompileProbe(metrics=metrics) if quant_on else None
    offs_j = jnp.asarray(offs)

    table = BankedTable(packed=params["emb_packed"],
                        remap_bank=statics["remap_bank"],
                        remap_slot=statics["remap_slot"],
                        n_banks=banks, rows_per_bank=cap)
    rcfg = ReplanConfig.for_vocab(V, banks, capacity_rows=cap,
                                  check_every=args.replan_every,
                                  hysteresis=args.hysteresis,
                                  quant=qspec,
                                  quant_dim=cfg.embed_dim if quant_on
                                  else None)
    runtime = AdaptiveEmbeddingRuntime(table, plan, rcfg,
                                       init_freq=np.ones(V),
                                       tracer=tracer, metrics=metrics)
    row_nbytes = (params["emb_packed"].shape[-1]
                  * np.dtype(params["emb_packed"].dtype).itemsize)
    slo = _TrafficSLO(args, metrics, tracer, banks=banks, dim=cfg.embed_dim,
                      row_nbytes=row_nbytes, runtime=runtime)

    # remap vectors (and on --quant the whole TieredTable) enter as
    # ARGUMENTS: a swap feeds new arrays of the same shape to the same
    # executable — zero recompiles across replans / re-tiers
    if quant_on:
        from repro.serve.serve_step import build_recsys_serve_tiered_adaptive
        serve_tiered = jax.jit(build_recsys_serve_tiered_adaptive(
            mod, cfg, statics, backend=args.backend, with_traffic=True))
    else:
        from repro.obs.traffic import bank_read_counts

        @jax.jit
        def serve(params, remap_bank, remap_slot, batch):
            st = {**statics, "remap_bank": remap_bank,
                  "remap_slot": remap_slot}
            logits = mod.forward(cfg, params, st, batch,
                                 backend=args.backend)
            sparse = batch["sparse"]
            o = offs_j[None, :] if sparse.ndim == 2 else offs_j[None, :, None]
            rows = jnp.where(sparse >= 0, sparse + o, -1)
            return jax.nn.sigmoid(logits), bank_read_counts(
                remap_bank, rows, banks)

    def observe(feats, n_real):
        sp = np.asarray(feats["sparse"])[:n_real]        # (n, F) or (n, F, L)
        runtime.observe_batch(rows_from_sparse(sp, offs))

    from repro.serve.serve_step import MicroBatcher, Request
    mh = max(cfg.multi_hot, 1)
    traces = [DriftingZipfTrace(
        DriftConfig(n_items=v, zipf_a=1.05, avg_bag=float(mh),
                    rotate_every=args.drift_rotate_every, rotate_frac=0.25),
        seed=args.seed + f) for f, v in enumerate(cfg.vocab_sizes)]
    rng = np.random.default_rng(args.seed)

    def one_request(rid):
        sparse = dlrm_drifting_batch(traces, 1, cfg.multi_hot)[0]
        return {"dense": rng.standard_normal(cfg.n_dense).astype(np.float32),
                "sparse": sparse}

    pad = one_request(-1)
    mb = MicroBatcher(args.batch, pad, observer=observe, metrics=metrics)
    verify: dict = {}
    state = {"warm_compiles": None, "n_batches": 0}

    def check_retier(event) -> None:
        """First-swap invariant: the incrementally re-tiered table is
        bit-identical to a from-scratch quantization of the migrated fp
        table under the same tier map."""
        from repro.quant import build_tiered_table
        tt = runtime.tiered
        fresh = build_tiered_table(runtime.table, tt.tier_of_row(),
                                   hot_dtype=tt.hot_dtype)
        ok = ((np.asarray(tt.payload) == np.asarray(fresh.payload)).all()
              and (np.asarray(tt.scale) == np.asarray(fresh.scale)).all()
              and (np.asarray(tt.tier) == np.asarray(fresh.tier)).all())
        verify["tier_ok"] = bool(ok)
        print(f"  [re-tier parity] {'OK' if ok else 'MISMATCH'} "
              f"(tier v{event.tier_version})")

    def run_batch():
        with tracer.span("rewrite"):
            reqs, feats = mb.next_batch()
        t0 = time.perf_counter()
        with tracer.span("device_step", batch=state["n_batches"]):
            p = {**params, "emb_packed": runtime.table.packed}
            if quant_on:
                scores, reads, nbytes = serve_tiered(p, runtime.tiered, feats)
            else:
                scores, reads = serve(p, runtime.table.remap_bank,
                                      runtime.table.remap_slot, feats)
                nbytes = None
            jax.block_until_ready(scores)
        wall_us = (time.perf_counter() - t0) * 1e6
        if quant_on and state["warm_compiles"] is None:
            state["warm_compiles"] = probe.compiles
        mb.complete(reqs)
        slo.after_step(state["n_batches"], reads, wall_us, args.batch,
                       nbytes=None if nbytes is None else np.asarray(nbytes),
                       p99_ms=mb.p99() * 1e3)
        state["n_batches"] += 1
        if writer is not None:
            writer.maybe_write(state["n_batches"])
        event = runtime.end_batch()        # drift check -> migrate -> swap
        if event is not None:
            slo.on_swap(runtime)
            msg = (f"  [swap @batch {event.batch}] {event.update.report} "
                   f"imbalance {event.old_imbalance:.3f} -> "
                   f"{event.new_imbalance:.3f}")
            if event.tier_version is not None:
                msg += (f"  tiers v{event.tier_version} "
                        f"+{event.tier_promoted}/-{event.tier_demoted} "
                        f"(requant {event.tier_requantized})")
            print(msg)
            if quant_on and "tier_ok" not in verify:
                check_retier(event)

    for rid in range(args.requests):
        mb.submit(Request(rid=rid, features=one_request(rid)))
        if len(mb.queue) >= args.batch:
            run_batch()
    while mb.ready():
        run_batch()

    lat = sorted(mb.latencies)
    p50 = lat[len(lat) // 2] * 1e3
    rp = runtime.replanner
    print(f"served {len(lat)} requests  p50={p50:.2f}ms "
          f"p99={mb.p99() * 1e3:.2f}ms  replans={rp.n_replans} "
          f"skipped={rp.n_skipped_replans}")
    metrics.gauge("jax.serve_executables").set(
        (serve_tiered if quant_on else serve)._cache_size())
    _finalize_obs(args, tracer, metrics, writer, latencies=mb.latencies)
    if quant_on:
        n_swaps = len(runtime.swaps)
        executables = serve_tiered._cache_size()
        other = probe.compiles - (state["warm_compiles"] or probe.compiles)
        print(f"compile probe: {executables} serve executable(s) across "
              f"{n_swaps} re-tier swap(s) — "
              f"{'ZERO serve recompiles' if executables == 1 else 'RECOMPILED'}"
              f" ({other} host-side compiles outside the serve step); "
              f"re-tier parity: {verify.get('tier_ok', 'n/a')}")
        if args.min_swaps > 0:
            ok = (n_swaps >= args.min_swaps and executables == 1
                  and verify.get("tier_ok", False))
            if not ok:
                raise SystemExit(
                    f"tiered serve contract violated: swaps={n_swaps} "
                    f"(need >= {args.min_swaps}), serve executables="
                    f"{executables} (need 1), "
                    f"re-tier parity={verify.get('tier_ok')}")
    slo.check_contract(args.min_slo_breaches)


def _main_adaptive_replicated(args, spec, cfg, mod) -> None:
    """Hot-row-replicated serving under the adaptive loop: the runtime's
    replica lane maintains a versioned (ReplicatedPlan, ReplicatedTable)
    side state; every drifted replan re-picks the replicated set from live
    head mass and the WHOLE replicated pytree swaps as a jit argument —
    same zero-recompile contract as the remap/cache/tier lanes.

    Contracts (hard exit with --min-swaps): at least that many live swaps,
    ONE serve executable across every replica-count change, and the first
    swapped-in replicated table bit-identical to packing the migrated base
    table's rows from scratch under the same plan (including the serve
    OUTPUT on a held probe batch).
    """
    from repro.core.embedding import BankedTable, pack_replicated
    from repro.core.partitioning import non_uniform_partition
    from repro.serve.serve_step import (
        MicroBatcher, Request, build_recsys_serve_replicated_adaptive)
    from repro.workload import (AdaptiveEmbeddingRuntime, DriftConfig,
                                DriftingZipfTrace, ReplanConfig,
                                dlrm_drifting_batch, rows_from_sparse,
                                unpacked_rows)

    banks = args.banks
    V = cfg.total_vocab
    cap = int(np.ceil(V / banks) * (1.0 + args.capacity_slack))
    plan = non_uniform_partition(np.ones(V), banks, capacity_rows=cap)
    params, statics = mod.init_params(cfg, jax.random.key(args.seed),
                                      plan=plan, rows_per_bank=cap)
    offs = np.asarray(statics["field_offsets"])

    tracer, metrics, writer = _setup_obs(
        args, label=f"serve-replicated:{args.arch}:k={args.replicate_k_max}")
    probe = CompileProbe(metrics=metrics)
    table = BankedTable(packed=params["emb_packed"],
                        remap_bank=statics["remap_bank"],
                        remap_slot=statics["remap_slot"],
                        n_banks=banks, rows_per_bank=cap)
    rcfg = ReplanConfig.for_vocab(V, banks, capacity_rows=cap,
                                  check_every=args.replan_every,
                                  hysteresis=args.hysteresis,
                                  replicate_k_max=args.replicate_k_max,
                                  replicate_max_r=args.replicate_max_r)
    runtime = AdaptiveEmbeddingRuntime(table, plan, rcfg,
                                       init_freq=np.ones(V),
                                       tracer=tracer, metrics=metrics)

    # the WHOLE replicated pytree (packed copies + (vocab, k_max) remap)
    # enters as an ARGUMENT; bank_live composes the fault lane in (all-live
    # here — failover behavior is pinned by tests/test_replication.py)
    serve = jax.jit(build_recsys_serve_replicated_adaptive(
        mod, cfg, statics, backend=args.backend, with_traffic=True))
    all_live = jnp.ones(banks, dtype=bool)
    row_nbytes = (params["emb_packed"].shape[-1]
                  * np.dtype(params["emb_packed"].dtype).itemsize)
    slo = _TrafficSLO(args, metrics, tracer, banks=banks, dim=cfg.embed_dim,
                      row_nbytes=row_nbytes, runtime=runtime)

    def observe(feats, n_real):
        sp = np.asarray(feats["sparse"])[:n_real]
        runtime.observe_batch(rows_from_sparse(sp, offs))

    mh = max(cfg.multi_hot, 1)
    # a much heavier head than the plain loop: replication only matters when
    # SINGLE rows carry > 1/(banks * k_max) of total traffic — with F fields
    # diluting each row to ~1/F of the stream, the per-field head must be
    # steep (zipf 2.0) before any one row crosses that line. Milder streams
    # correctly replicate nothing (copies all 1 — bit-identical serving).
    traces = [DriftingZipfTrace(
        DriftConfig(n_items=v, zipf_a=2.0, avg_bag=float(mh),
                    rotate_every=args.drift_rotate_every, rotate_frac=0.25),
        seed=args.seed + f) for f, v in enumerate(cfg.vocab_sizes)]
    rng = np.random.default_rng(args.seed)

    def one_request(rid):
        sparse = dlrm_drifting_batch(traces, 1, cfg.multi_hot)[0]
        return {"dense": rng.standard_normal(cfg.n_dense).astype(np.float32),
                "sparse": sparse}

    mb = MicroBatcher(args.batch, one_request(-1), observer=observe,
                      metrics=metrics)
    verify: dict = {}
    state = {"warm_compiles": None, "n_batches": 0}

    def check_repack(event) -> None:
        """First-swap invariant: the replica-lane table is bit-identical to
        packing the migrated base table's rows from scratch under the same
        plan — including the serve output on the probe batch."""
        rplan, rtable = runtime.replicated
        fresh = pack_replicated(unpacked_rows(runtime.table), rplan,
                                rows_per_bank=cap)
        arrays_ok = ((np.asarray(rtable.packed)
                      == np.asarray(fresh.packed)).all()
                     and (np.asarray(rtable.remap_bank)
                          == np.asarray(fresh.remap_bank)).all()
                     and (np.asarray(rtable.remap_slot)
                          == np.asarray(fresh.remap_slot)).all())
        feats = verify["feats"]
        swapped, _, _ = serve(params, rtable, all_live, feats)
        scratch, _, _ = serve(params, fresh, all_live, feats)
        out_ok = (np.asarray(swapped) == np.asarray(scratch)).all()
        verify["repack_ok"] = bool(arrays_ok and out_ok)
        print(f"  [replica swap parity] arrays "
              f"{'OK' if arrays_ok else 'MISMATCH'}  outputs "
              f"{'OK' if out_ok else 'MISMATCH'} "
              f"(replica v{event.replica_version})")

    def run_batch():
        with tracer.span("rewrite"):
            reqs, feats = mb.next_batch()
        t0 = time.perf_counter()
        with tracer.span("device_step", batch=state["n_batches"]):
            _, rtable = runtime.replicated
            scores, counts, reads = serve(params, rtable, all_live, feats)
            jax.block_until_ready(scores)
        wall_us = (time.perf_counter() - t0) * 1e6
        assert int(np.asarray(counts).sum()) == 0  # all-live: no degradation
        if state["warm_compiles"] is None:
            state["warm_compiles"] = probe.compiles
        mb.complete(reqs)
        slo.after_step(state["n_batches"], reads, wall_us, args.batch,
                       p99_ms=mb.p99() * 1e3)
        state["n_batches"] += 1
        if writer is not None:
            writer.maybe_write(state["n_batches"])
        event = runtime.end_batch()        # drift check -> migrate -> swap
        if event is not None:
            slo.on_swap(runtime)
            rplan, _ = runtime.replicated
            print(f"  [swap @batch {event.batch}] {event.update.report} "
                  f"imbalance {event.old_imbalance:.3f} -> "
                  f"{event.new_imbalance:.3f}  replicas v"
                  f"{event.replica_version} hot={event.replica_hot_rows} "
                  f"churn={event.replica_copy_churn} "
                  f"modeled share={rplan.max_share():.4f} "
                  f"(ideal {1.0 / banks:.4f})")
            if "repack_ok" not in verify:
                verify["feats"] = feats
                check_repack(event)

    for rid in range(args.requests):
        mb.submit(Request(rid=rid, features=one_request(rid)))
        if len(mb.queue) >= args.batch:
            run_batch()
    while mb.ready():
        run_batch()

    lat = sorted(mb.latencies)
    p50 = lat[len(lat) // 2] * 1e3
    rp = runtime.replanner
    n_swaps = len(runtime.swaps)
    executables = serve._cache_size()
    other = probe.compiles - (state["warm_compiles"] or probe.compiles)
    rplan, _ = runtime.replicated
    print(f"served {len(lat)} requests  p50={p50:.2f}ms "
          f"p99={mb.p99() * 1e3:.2f}ms  replans={rp.n_replans} "
          f"skipped={rp.n_skipped_replans}")
    print(f"replica lane: v{runtime.replica_version}, "
          f"{rplan.n_replicated} replicated row(s) "
          f"(k_max {args.replicate_k_max}), modeled max-bank share "
          f"{rplan.max_share():.4f} vs ideal {1.0 / banks:.4f}")
    print(f"compile probe: {executables} serve executable(s) across "
          f"{n_swaps} replica swap(s) — "
          f"{'ZERO serve recompiles' if executables == 1 else 'RECOMPILED'} "
          f"({other} host-side compiles outside the serve step); "
          f"re-pack parity: {verify.get('repack_ok', 'n/a')}")
    metrics.gauge("jax.serve_executables").set(executables)
    _finalize_obs(args, tracer, metrics, writer, latencies=mb.latencies)
    if args.min_swaps > 0:
        ok = (n_swaps >= args.min_swaps and executables == 1
              and verify.get("repack_ok", False))
        if not ok:
            raise SystemExit(
                f"replicated serve contract violated: swaps={n_swaps} "
                f"(need >= {args.min_swaps}), serve executables="
                f"{executables} (need 1), "
                f"re-pack parity={verify.get('repack_ok')}")
    slo.check_contract(args.min_slo_breaches)


def _main_adaptive_fault(args, spec, cfg, mod) -> None:
    """Fault-tolerant serving: the adaptive loop with an injected per-bank
    fault schedule. The serve step takes a ``bank_live`` mask as one more
    swap-style ARGUMENT and returns (scores, degraded_read_count); a bank
    death triggers the recovery replan (rows re-packed onto survivors
    through the versioned migrate/swap lane), and degraded-slow banks are
    caught by the StragglerWatchdog and shed load via planner penalties.

    Contracts (hard exit with --min-recoveries): degradation confined to
    dead-bank rows (count==0 requests bit-match a never-failed run even
    MID-FAILURE), post-recovery batches fully bit-match a never-failed run
    with zero degraded reads, and the whole failure -> replan -> recovery
    cycle uses ONE serve executable. The never-failed reference is the same
    executable evaluated against the ORIGINAL pack + all-live mask — the
    unsharded bag scan sums bag entries in index order whatever the plan, so
    cross-plan bit-parity is exact, not approximate.
    """
    from repro.core.embedding import BankedTable
    from repro.core.partitioning import non_uniform_partition
    from repro.dist.bank_fault import BankFaultState
    from repro.dist.fault import StragglerWatchdog
    from repro.serve.serve_step import (MicroBatcher, Request,
                                        build_recsys_serve_degraded_adaptive)
    from repro.workload import (AdaptiveEmbeddingRuntime, DriftConfig,
                                DriftingZipfTrace, ReplanConfig,
                                dlrm_drifting_batch, rows_from_sparse)

    banks = args.banks
    V = cfg.total_vocab
    cap = int(np.ceil(V / banks) * (1.0 + args.capacity_slack))
    plan = non_uniform_partition(np.ones(V), banks, capacity_rows=cap)
    params, statics = mod.init_params(cfg, jax.random.key(args.seed),
                                      plan=plan, rows_per_bank=cap)
    offs = np.asarray(statics["field_offsets"])
    fault = BankFaultState.from_specs(banks, args.inject_bank_failure)
    tracer, metrics, writer = _setup_obs(
        args, label=f"serve-fault:{args.arch}")
    probe = CompileProbe(metrics=metrics)
    # fault-lane counters the final snapshot/summary must always carry,
    # fired or not (the CI metrics-schema gate keys on them)
    m_deg_reads = metrics.counter("serve.degraded_reads_total",
                                  "bounded-degraded row reads served")
    m_deg_batches = metrics.counter("serve.degraded_batches_total",
                                    "micro-batches with >0 degraded reads")
    m_faults = metrics.counter("fault.injected_total",
                               "bank-fault schedule events fired")

    table = BankedTable(packed=params["emb_packed"],
                        remap_bank=statics["remap_bank"],
                        remap_slot=statics["remap_slot"],
                        n_banks=banks, rows_per_bank=cap)
    rcfg = ReplanConfig.for_vocab(V, banks, capacity_rows=cap,
                                  check_every=args.replan_every,
                                  hysteresis=args.hysteresis)
    runtime = AdaptiveEmbeddingRuntime(table, plan, rcfg,
                                       init_freq=np.ones(V),
                                       tracer=tracer, metrics=metrics)
    watchdog = StragglerWatchdog(factor=args.straggler_factor,
                                 metrics=metrics)

    serve = jax.jit(build_recsys_serve_degraded_adaptive(
        mod, cfg, statics, backend=args.backend, with_traffic=True))
    all_live = jnp.ones(banks, dtype=bool)
    row_nbytes = (params["emb_packed"].shape[-1]
                  * np.dtype(params["emb_packed"].dtype).itemsize)
    slo = _TrafficSLO(args, metrics, tracer, banks=banks, dim=cfg.embed_dim,
                      row_nbytes=row_nbytes, runtime=runtime)
    # the never-failed reference pack: same executable, original arrays
    orig = (params["emb_packed"], statics["remap_bank"],
            statics["remap_slot"])

    def observe(feats, n_real):
        sp = np.asarray(feats["sparse"])[:n_real]
        runtime.observe_batch(rows_from_sparse(sp, offs))

    mh = max(cfg.multi_hot, 1)
    traces = [DriftingZipfTrace(
        DriftConfig(n_items=v, zipf_a=1.05, avg_bag=float(mh),
                    rotate_every=args.drift_rotate_every, rotate_frac=0.25),
        seed=args.seed + f) for f, v in enumerate(cfg.vocab_sizes)]
    rng = np.random.default_rng(args.seed)

    def one_request(rid):
        sparse = dlrm_drifting_batch(traces, 1, cfg.multi_hot)[0]
        return {"dense": rng.standard_normal(cfg.n_dense).astype(np.float32),
                "sparse": sparse}

    mb = MicroBatcher(args.batch, one_request(-1), observer=observe,
                      metrics=metrics)
    st = {"batch": 0, "handled_dead": frozenset(), "penalized": False,
          "fail_batch": None, "recover_batch": None,
          "confine_ok": True, "confine_checked": 0,
          "recover_parity": None, "degraded_reads": 0, "degraded_batches": 0}
    recoveries: list = []

    def never_failed(feats):
        p0 = {**params, "emb_packed": orig[0]}
        ref, _, _ = serve(p0, orig[1], orig[2], all_live, feats)
        return np.asarray(ref)

    def run_batch():
        b = st["batch"]
        st["batch"] += 1
        for e in fault.advance(b):
            print(f"  [fault @batch {b}] {e}")
            m_faults.inc()
            tracer.instant("fault_injected", batch=b, event=str(e))
            if st["fail_batch"] is None and fault.dead_banks():
                st["fail_batch"] = b
        live = fault.live_mask()
        with tracer.span("rewrite"):
            reqs, feats = mb.next_batch()
        t0 = time.perf_counter()
        with tracer.span("device_step", batch=b):
            p = {**params, "emb_packed": runtime.table.packed}
            scores, counts, reads = serve(p, runtime.table.remap_bank,
                                          runtime.table.remap_slot,
                                          jnp.asarray(live), feats)
            jax.block_until_ready(scores)
        wall_us = (time.perf_counter() - t0) * 1e6
        slo.after_step(b, reads, wall_us, args.batch,
                       p99_ms=mb.p99() * 1e3)
        if writer is not None:
            writer.maybe_write(st["batch"])
        counts = np.asarray(counts)
        n_deg = int(counts.sum())
        st["degraded_reads"] += n_deg
        m_deg_reads.inc(n_deg)
        if n_deg > 0:
            st["degraded_batches"] += 1
            m_deg_batches.inc()
            # confinement: requests that touched NO dead-bank row must be
            # bit-exact vs the never-failed run, mid-failure included
            if st["confine_checked"] < 2:
                st["confine_checked"] += 1
                ref = never_failed(feats)
                exact = np.asarray(scores)[counts == 0] == ref[counts == 0]
                ok = bool(exact.all()) and (counts > 0).any()
                st["confine_ok"] = st["confine_ok"] and ok
                print(f"  [degraded @batch {b}] {n_deg} degraded reads, "
                      f"{int((counts > 0).sum())}/{len(counts)} requests; "
                      f"clean requests bit-exact: {ok}")
        elif st["recover_batch"] is None and st["fail_batch"] is not None \
                and st["handled_dead"]:
            # first clean batch after the recovery swap: full bit-parity
            st["recover_batch"] = b
            ref = never_failed(feats)
            st["recover_parity"] = bool(
                (np.asarray(scores) == ref).all())
            print(f"  [recovered @batch {b}] 0 degraded reads "
                  f"({b - st['fail_batch']} batches after failure); "
                  f"bit-parity with never-failed run: "
                  f"{st['recover_parity']}")
        mb.complete(reqs)

        # recovery lane: any not-yet-handled bank death replans NOW
        dead = frozenset(fault.dead_banks())
        if dead != st["handled_dead"]:
            event = runtime.on_bank_failure(live)
            slo.on_swap(runtime)
            st["handled_dead"] = dead
            recoveries.append(event)
            print(f"  [recovery replan @batch {b}] dead={sorted(dead)} "
                  f"reason={event.reason} "
                  f"recovery={event.recovery_s * 1e3:.1f}ms "
                  f"imbalance {event.old_imbalance:.3f} -> "
                  f"{event.new_imbalance:.3f}")
            return
        # straggler lane: modeled per-bank batch time (reads x slow factor;
        # banks run in parallel, so the batch takes the slowest bank's
        # time). The watchdog sees EVERY batch — healthy batches build the
        # median baseline a degraded bank must then exceed.
        sf = fault.slow_factor()
        rows = rows_from_sparse(np.asarray(feats["sparse"]), offs)
        rows = rows[rows >= 0]
        reads = np.bincount(
            np.asarray(runtime.plan.bank_of_row)[rows], minlength=banks)
        t_bank = reads.astype(np.float64) * sf
        if watchdog.observe(b, float(t_bank.max())) and not st["penalized"]:
            slow = int(np.argmax(t_bank))
            pen = np.ones(banks)
            pen[slow] = float(max(sf[slow], 1.0))
            event = runtime.on_straggler(pen)
            slo.on_swap(runtime)
            st["penalized"] = True
            print(f"  [straggler @batch {b}] bank {slow} flagged "
                  f"(x{pen[slow]:g}); penalty replan "
                  f"imbalance {event.old_imbalance:.3f} -> "
                  f"{event.new_imbalance:.3f}")
            return
        event = runtime.end_batch()            # ordinary drift lane
        if event is not None:
            slo.on_swap(runtime)
            print(f"  [swap @batch {event.batch}] {event.update.report} "
                  f"imbalance {event.old_imbalance:.3f} -> "
                  f"{event.new_imbalance:.3f}")

    for rid in range(args.requests):
        mb.submit(Request(rid=rid, features=one_request(rid)))
        if len(mb.queue) >= args.batch:
            run_batch()
    while mb.ready():
        run_batch()

    lat = sorted(mb.latencies)
    p50 = lat[len(lat) // 2] * 1e3
    rp = runtime.replanner
    executables = serve._cache_size()
    n_rec = len([e for e in recoveries if e.reason == "bank_failure"])
    print(f"served {len(lat)} requests  p50={p50:.2f}ms "
          f"p99={mb.p99() * 1e3:.2f}ms  replans={rp.n_replans} "
          f"skipped={rp.n_skipped_replans}")
    print(f"fault lane: {len(fault.fired)} fault(s) fired, "
          f"{st['degraded_reads']} degraded reads over "
          f"{st['degraded_batches']} batch(es), {n_rec} recovery replan(s), "
          f"{len(watchdog.events)} straggler event(s); "
          f"confinement {'OK' if st['confine_ok'] else 'VIOLATED'}, "
          f"recovery parity {st['recover_parity']}, "
          f"{executables} serve executable(s)")
    print(f"slo lane: {slo.breaches} breach(es) over "
          f"{slo.acc.batches} measured batch(es), "
          f"{slo.penalties} replanner penalt(ies)")
    metrics.gauge("jax.serve_executables").set(executables)
    _finalize_obs(args, tracer, metrics, writer, latencies=mb.latencies)
    if args.min_recoveries > 0:
        ok = (n_rec >= args.min_recoveries and executables == 1
              and st["confine_ok"] and st["recover_parity"] is True)
        if not ok:
            raise SystemExit(
                f"fault-serve contract violated: recoveries={n_rec} "
                f"(need >= {args.min_recoveries}), serve executables="
                f"{executables} (need 1), confinement={st['confine_ok']}, "
                f"recovery parity={st['recover_parity']}")
    slo.check_contract(args.min_slo_breaches)


def _main_adaptive_cached(args, spec, cfg, mod) -> None:
    """The fused cache+residual serve path under the adaptive runtime: every
    batch host-rewritten + version-tagged, live GRACE-table swaps between
    micro-batches, one serve executable for the whole run."""
    from repro.core.cache_runtime import build_cache_table_fixed
    from repro.core.embedding import BankedTable
    from repro.core.partitioning import non_uniform_partition
    from repro.serve.serve_step import build_recsys_serve_cached_adaptive
    from repro.workload import (AdaptiveEmbeddingRuntime, DriftConfig,
                                DriftingZipfTrace, ReplanConfig,
                                dlrm_drifting_batch, unpacked_rows)

    mh = cfg.multi_hot
    assert mh >= 2, ("--partition cache_aware needs multi-hot bags "
                     "(try --arch updlrm-paper); GRACE partial sums fuse "
                     ">=2 lookups of one bag")
    banks = args.banks
    V = cfg.total_vocab
    cap = int(np.ceil(V / banks) * (1.0 + args.capacity_slack))
    crpb = max(1, -(-args.cache_entries // banks))
    plan = non_uniform_partition(np.ones(V), banks, capacity_rows=cap)
    params, statics = mod.init_params(cfg, jax.random.key(args.seed),
                                      plan=plan, rows_per_bank=cap)
    offs = np.asarray(statics["field_offsets"])

    tracer, metrics, writer = _setup_obs(
        args, label=f"serve-cached:{args.arch}")
    probe = CompileProbe(metrics=metrics)
    table = BankedTable(packed=params["emb_packed"],
                        remap_bank=statics["remap_bank"],
                        remap_slot=statics["remap_slot"],
                        n_banks=banks, rows_per_bank=cap)
    rcfg = ReplanConfig.for_vocab(V, banks, capacity_rows=cap,
                                  check_every=args.replan_every,
                                  partitioner="cache_aware",
                                  cache_rows_per_bank=crpb,
                                  mine_min_support=2,
                                  hysteresis=args.hysteresis,
                                  # exponential window: a long-lived server's
                                  # cumulative estimate goes blind to late
                                  # rotations (bench_workload's p99 spike)
                                  telemetry_decay=0.8,
                                  telemetry_decay_every=4096)
    runtime = AdaptiveEmbeddingRuntime(
        table, plan, rcfg, init_freq=np.ones(V),
        max_cache_per_bag=max(2, mh // 4), max_residual_per_bag=mh,
        tracer=tracer, metrics=metrics)

    serve = jax.jit(build_recsys_serve_cached_adaptive(
        mod, cfg, statics, backend=args.backend, with_traffic=True))
    row_nbytes = (params["emb_packed"].shape[-1]
                  * np.dtype(params["emb_packed"].dtype).itemsize)
    slo = _TrafficSLO(args, metrics, tracer, banks=banks, dim=cfg.embed_dim,
                      row_nbytes=row_nbytes, runtime=runtime)

    def union_rect(feats):
        sp = np.asarray(feats["sparse"])                 # (B, F, L)
        return np.where(sp >= 0, sp + offs[None, :, None], -1)

    def observe(feats, n_real):
        sp = np.asarray(feats["sparse"])[:n_real]
        u = np.where(sp >= 0, sp + offs[None, :, None], -1)
        runtime.observe_bags([bag[bag >= 0]
                              for bag in u.reshape(-1, u.shape[-1])])

    traces = [DriftingZipfTrace(
        DriftConfig(n_items=v, zipf_a=1.2, avg_bag=float(mh),
                    rotate_every=args.drift_rotate_every, rotate_frac=0.25),
        seed=args.seed + f) for f, v in enumerate(cfg.vocab_sizes)]
    rng = np.random.default_rng(args.seed)

    def one_request(rid):
        sparse = dlrm_drifting_batch(traces, 1, mh)[0]
        return {"dense": rng.standard_normal(cfg.n_dense).astype(np.float32),
                "sparse": sparse}

    mb = MicroBatcher(args.batch, one_request(-1), observer=observe,
                      metrics=metrics)
    verify: dict = {}
    state = {"warm_compiles": None, "n_batches": 0}

    def check_swap(event) -> None:
        """First-swap invariant: the swapped-in state is bit-identical to a
        from-scratch rebuild of the whole cache path at the same plan."""
        rows = unpacked_rows(runtime.table)
        p = runtime.plan
        fresh = np.zeros_like(np.asarray(runtime.table.packed))
        fresh[p.bank_of_row.astype(np.int64) * cap + p.slot_of_row] = rows
        emt_ok = (np.asarray(runtime.table.packed) == fresh).all()
        fresh_cache = build_cache_table_fixed(rows, runtime.cache_plan,
                                              dtype=fresh.dtype)
        ct = runtime.cache_table
        cache_ok = ((np.asarray(ct.packed)
                     == np.asarray(fresh_cache.packed)).all()
                    and (np.asarray(ct.remap_bank)
                         == np.asarray(fresh_cache.remap_bank)).all()
                    and (np.asarray(ct.remap_slot)
                         == np.asarray(fresh_cache.remap_slot)).all())
        verify.update(arrays_ok=bool(emt_ok and cache_ok),
                      fresh_cache=fresh_cache, version=runtime.rewriter.version)
        print(f"  [swap parity] EMT {'OK' if emt_ok else 'MISMATCH'}  "
              f"cache {'OK' if cache_ok else 'MISMATCH'} "
              f"(version {verify['version']})")

    def run_batch():
        with tracer.span("rewrite"):
            reqs, feats = mb.next_batch()
            rb = runtime.rewrite(union_rect(feats))      # host pipeline, v
        event = runtime.end_batch()                      # may swap to v+1
        if event is not None:
            slo.on_swap(runtime)
            hits = int((rb.cache_idx >= 0).sum())
            print(f"  [swap @batch {event.batch}] {event.update.report} "
                  f"imbalance {event.old_imbalance:.3f} -> "
                  f"{event.new_imbalance:.3f}  cache v{event.cache_version} "
                  f"entries {event.cache_entries} "
                  f"(dropped {event.cache_dropped}, in-flight hits {hits})")
            if "arrays_ok" not in verify:
                check_swap(event)
                verify["feats"] = feats                  # output-parity probe
                verify["rb"] = runtime.rewrite(union_rect(feats))
                verify["table"] = runtime.cache_table    # the swapped-in one
        # the in-flight batch resolves against ITS version's cache table,
        # even when the swap above just retired it from "current"
        t0 = time.perf_counter()
        with tracer.span("device_step", batch=state["n_batches"],
                         cache_version=rb.version):
            batch_c = {"dense": feats["dense"],
                       "cache_idx": jnp.asarray(rb.cache_idx),
                       "residual_idx": jnp.asarray(rb.residual_idx)}
            p = {**params, "emb_packed": runtime.table.packed}
            scores, reads = serve(p, runtime.table.remap_bank,
                                  runtime.table.remap_slot,
                                  runtime.cache_table_for(rb.version), batch_c)
            jax.block_until_ready(scores)
        wall_us = (time.perf_counter() - t0) * 1e6
        if state["warm_compiles"] is None:
            state["warm_compiles"] = probe.compiles      # post-first-compile
        mb.complete(reqs)
        slo.after_step(state["n_batches"], reads, wall_us, args.batch,
                       p99_ms=mb.p99() * 1e3)
        state["n_batches"] += 1
        if writer is not None:
            writer.maybe_write(state["n_batches"])

    for rid in range(args.requests):
        mb.submit(Request(rid=rid, features=one_request(rid)))
        if len(mb.queue) >= args.batch:
            run_batch()
    while mb.ready():
        run_batch()

    # -- post-run invariants -------------------------------------------------
    n_swaps = len(runtime.swaps)
    executables = serve._cache_size()       # 1 == zero serve-step recompiles
    other_compiles = probe.compiles - (state["warm_compiles"]
                                       or probe.compiles)
    out_ok = True
    if verify:
        rb = verify["rb"]
        batch_c = {"dense": verify["feats"]["dense"],
                   "cache_idx": jnp.asarray(rb.cache_idx),
                   "residual_idx": jnp.asarray(rb.residual_idx)}
        p = {**params, "emb_packed": runtime.table.packed}
        swapped, _ = serve(p, runtime.table.remap_bank,
                           runtime.table.remap_slot, verify["table"], batch_c)
        fresh, _ = serve(p, runtime.table.remap_bank, runtime.table.remap_slot,
                         verify["fresh_cache"], batch_c)
        out_ok = bool((np.asarray(swapped) == np.asarray(fresh)).all())

    lat = sorted(mb.latencies)
    p50 = lat[len(lat) // 2] * 1e3
    print(f"served {len(lat)} requests  p50={p50:.2f}ms "
          f"p99={mb.p99() * 1e3:.2f}ms  replans={runtime.replanner.n_replans} "
          f"skipped={runtime.replanner.n_skipped_replans} "
          f"swaps={n_swaps}  cache entries={runtime.cache_plan.n_entries}")
    print(f"compile probe: {executables} serve executable(s) across "
          f"{n_swaps} swap(s) — "
          f"{'ZERO serve recompiles' if executables == 1 else 'RECOMPILED'} "
          f"({other_compiles} host-side compiles outside the serve step, "
          f"migration collectives included); swap parity: "
          f"arrays {'OK' if verify.get('arrays_ok') else 'n/a'}, "
          f"outputs {'OK' if out_ok else 'MISMATCH'}")
    metrics.gauge("jax.serve_executables").set(executables)
    _finalize_obs(args, tracer, metrics, writer, latencies=mb.latencies)
    if args.min_swaps > 0:
        ok = (n_swaps >= args.min_swaps and executables == 1 and out_ok
              and verify.get("arrays_ok", False))
        if not ok:
            raise SystemExit(
                f"serve-smoke contract violated: swaps={n_swaps} "
                f"(need >= {args.min_swaps}), serve executables="
                f"{executables} (need 1), "
                f"parity={verify.get('arrays_ok')}/{out_ok}")
    slo.check_contract(args.min_slo_breaches)


def _one(spec, cfg, rng, rid):
    from repro.data import synthetic as syn
    if spec.family == "dlrm":
        b = syn.dlrm_batch(cfg.vocab_sizes, cfg.n_dense, 1, seed=1, step=rid,
                           multi_hot=cfg.multi_hot)
    elif spec.family == "din":
        b = syn.din_batch(cfg.n_items, cfg.n_cates, cfg.seq_len, 1, seed=1,
                          step=rid)
    else:
        b = syn.xdeepfm_batch(cfg.vocab_sizes, 1, seed=1, step=rid)
    b.pop("label", None)
    return b


if __name__ == "__main__":
    main()
