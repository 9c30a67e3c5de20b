"""Serve-step builders per family + a micro-batching request queue.

The recsys serve path is the paper's object of study: p99-latency online
inference (batch 512), offline bulk scoring (262k), and retrieval scoring
(1 query x 1M candidates). The LM paths are prefill and KV-cache decode.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable

import jax
import jax.numpy as jnp


def serve_tables(statics: dict) -> dict:
    """The array entries of a family's ``statics`` (remap vectors, field
    offsets): the ``tables`` argument of ``build_recsys_serve``'s step."""
    return {k: v for k, v in statics.items() if isinstance(v, jax.Array)}


def build_recsys_serve(family_mod, cfg, statics, dist=None,
                       backend: str | None = None):
    """CTR scoring: forward + sigmoid, as ``serve(params, tables, batch)``.

    ``tables`` is ``serve_tables(statics)``. The remap vectors enter as jit
    arguments because JAX embeds closed-over arrays in the program as
    constants: 75.5 MB per vector at updlrm-paper's published vocab.

    ``backend`` selects the embedding stage-2 implementation for families
    that expose the knob (dlrm: 'jnp' | 'pallas' | 'auto'); None keeps the
    family default.
    """
    kw = {} if backend is None else {"backend": backend}
    scalars = {k: v for k, v in statics.items() if not isinstance(v, jax.Array)}

    def serve(params, tables, batch):
        logits = family_mod.forward(cfg, params, {**scalars, **tables}, batch,
                                    dist, **kw)
        return jax.nn.sigmoid(logits)
    return serve


def build_recsys_serve_cached(family_mod, cfg, statics, cache_table,
                              dist=None, backend: str | None = None):
    """Cache-aware CTR scoring (Fig. 7): requests pre-rewritten into
    (cache_idx, residual_idx) bags by the host pipeline."""
    kw = {} if backend is None else {"backend": backend}

    def serve(params, batch):
        logits = family_mod.forward_cached(cfg, params, statics, cache_table,
                                           batch, dist, **kw)
        return jax.nn.sigmoid(logits)
    return serve


def build_recsys_serve_cached_adaptive(family_mod, cfg, statics, dist=None,
                                       backend: str | None = None,
                                       with_traffic: bool = False):
    """Cache-aware CTR scoring under the ADAPTIVE runtime: everything a live
    swap replaces — the EMT remap vectors AND the GRACE cache table — enters
    as an argument of the returned ``serve(params, remap_bank, remap_slot,
    cache_table, batch)``, never as a closure constant. Table shapes are
    pinned (fixed ``rows_per_bank`` on the EMT, fixed ``cache_rows_per_bank``
    on the cache side), so one jit compilation serves every plan version:
    a swap is a pure argument change.

    ``with_traffic=True`` (a BUILD-time flag, not a jit argument) appends a
    measured per-bank read-count vector to the step's outputs:
    ``(scores, bank_reads)``. The counts are pure jnp over the same
    remap/cache arguments the lookup consumes (obs/traffic.py), so the
    traffic-instrumented step still compiles ONE executable across swaps.
    """
    kw = {} if backend is None else {"backend": backend}

    def serve(params, remap_bank, remap_slot, cache_table, batch):
        logits = family_mod.forward_cached(
            cfg, params, statics, cache_table, batch, dist,
            remap_bank=remap_bank, remap_slot=remap_slot, **kw)
        if with_traffic:
            from repro.obs.traffic import cached_bank_read_counts
            reads = cached_bank_read_counts(
                cache_table.remap_bank, batch["cache_idx"],
                remap_bank, batch["residual_idx"], cache_table.n_banks)
            return jax.nn.sigmoid(logits), reads
        return jax.nn.sigmoid(logits)
    return serve


def build_recsys_serve_degraded_adaptive(family_mod, cfg, statics, dist=None,
                                         backend: str | None = None,
                                         with_traffic: bool = False):
    """CTR scoring that stays up through bank failures: the returned
    ``serve(params, remap_bank, remap_slot, bank_live, batch)`` takes the
    per-bank liveness mask as ONE MORE swap-style argument next to the remap
    vectors — reads homed on a dead bank resolve to the zero row
    (core/embedding.py's bounded-degradation contract), and the step returns
    ``(scores, degraded_read_count)`` so every response carries exactly how
    many row contributions it is missing (0 = bit-exact). All-live serving
    through this step is bit-identical to the non-degraded step — the fault
    lane compiles ONE executable and flips the mask argument.

    ``with_traffic=True`` (build-time flag) appends the measured per-bank
    read counts: ``(scores, degraded_counts, bank_reads)``. Reads resolved
    to the zero row on a dead bank are NOT counted as bank traffic (the bank
    never served them) — ``bank_reads.sum() + degraded_counts.sum()`` equals
    the batch's valid lookups.
    """
    from repro.core.embedding import degraded_row_counts
    kw = {} if backend is None else {"backend": backend}

    def serve(params, remap_bank, remap_slot, bank_live, batch):
        st = {**statics, "remap_bank": remap_bank, "remap_slot": remap_slot}
        logits = family_mod.forward(cfg, params, st, batch, dist,
                                    bank_live=bank_live, **kw)
        sparse = batch["sparse"]
        offs = st["field_offsets"]
        offs = offs[None, :] if sparse.ndim == 2 else offs[None, :, None]
        rows = jnp.where(sparse >= 0, sparse + offs, -1)
        counts = degraded_row_counts(remap_bank, bank_live, rows)
        if with_traffic:
            from repro.obs.traffic import bank_read_counts
            reads = bank_read_counts(remap_bank, rows, bank_live.shape[0],
                                     bank_live=bank_live)
            return jax.nn.sigmoid(logits), counts, reads
        return jax.nn.sigmoid(logits), counts
    return serve


def build_recsys_serve_tiered_adaptive(family_mod, cfg, statics, dist=None,
                                       backend: str | None = None,
                                       with_traffic: bool = False):
    """CTR scoring over TIERED-precision embeddings under the adaptive
    runtime: the whole TieredTable pytree — quantized payload, per-row
    scales, tier map, AND the remap vectors — enters as an argument of the
    returned ``serve(params, tiered, batch)``. Payload/scale/tier shapes
    depend only on (capacity, dim, hot dtype), never on the tier mix, so a
    live re-tier swap (hot rows promoted, cold rows demoted on drift) is a
    pure argument change against one compiled executable.

    ``with_traffic=True`` (build-time flag) appends measured per-bank reads
    AND bytes: ``(scores, bank_reads, bank_nbytes)``. Bytes weight each read
    by its row's CURRENT tier width (the tier map rides in the ``tiered``
    argument), so a re-tier swap shows up in the byte series immediately.
    """
    kw = {} if backend is None else {"backend": backend}

    def serve(params, tiered, batch):
        logits = family_mod.forward(cfg, params, statics, batch, dist,
                                    tiered=tiered, **kw)
        if with_traffic:
            from repro.obs.traffic import tiered_bank_traffic
            from repro.quant import tier_nbytes
            sparse = batch["sparse"]
            offs = statics["field_offsets"]
            offs = offs[None, :] if sparse.ndim == 2 else offs[None, :, None]
            rows = jnp.where(sparse >= 0, sparse + offs, -1)
            traffic = tiered_bank_traffic(
                tiered.remap_bank, tiered.remap_slot, tiered.rows_per_bank,
                tiered.tier, tier_nbytes(tiered.dim, tiered.hot_dtype),
                rows, tiered.n_banks)
            return jax.nn.sigmoid(logits), traffic.reads, traffic.nbytes
        return jax.nn.sigmoid(logits)
    return serve


def build_recsys_serve_replicated_adaptive(family_mod, cfg, statics,
                                           dist=None,
                                           backend: str | None = None,
                                           with_traffic: bool = False):
    """CTR scoring over HOT-ROW-REPLICATED embeddings under the adaptive
    runtime: the whole ReplicatedTable pytree — the packed copies plus the
    ``(vocab, k_max)`` replica-axis remap — enters as an argument of the
    returned ``serve(params, replicated, bank_live, batch)``. Map shapes
    depend only on (vocab, k_max) and the packed shape only on the fixed
    per-bank capacity, never on WHICH rows are replicated, so a live
    replica-count swap (telemetry found a new head) is a pure argument
    change against one compiled executable. ``bank_live`` composes the
    fault lane in: a surviving copy covers a dead bank's head reads
    instantly, and the step returns ``(scores, degraded_read_count)`` where
    a read only counts degraded when EVERY copy of the row is dead.

    ``with_traffic=True`` (build-time flag) appends the measured per-bank
    reads — ``(scores, degraded_counts, bank_reads)`` — attributed to the
    copy each bag ACTUALLY reads (the same deterministic bag-hash routing
    and dead-copy failover the kernel applies), so replication's load split
    and a failover's traffic shift are both visible in the series.
    """
    from repro.core.embedding import degraded_row_counts
    kw = {} if backend is None else {"backend": backend}

    def serve(params, replicated, bank_live, batch):
        logits = family_mod.forward(cfg, params, statics, batch, dist,
                                    replicated=replicated,
                                    bank_live=bank_live, **kw)
        sparse = batch["sparse"]
        offs = statics["field_offsets"]
        offs = offs[None, :] if sparse.ndim == 2 else offs[None, :, None]
        rows = jnp.where(sparse >= 0, sparse + offs, -1)
        counts = degraded_row_counts(replicated.remap_bank, bank_live, rows)
        if with_traffic:
            from repro.obs.traffic import replicated_bank_read_counts
            reads = replicated_bank_read_counts(
                replicated.remap_bank, rows, bank_live.shape[0],
                k_max=replicated.k_max, bank_live=bank_live)
            return jax.nn.sigmoid(logits), counts, reads
        return jax.nn.sigmoid(logits), counts
    return serve


def build_retrieval_serve(family_mod, cfg, statics, dist=None, top_k: int = 128):
    """1 query x N candidates -> (top-k scores, top-k ids)."""
    def serve(params, batch):
        scores = family_mod.retrieval_scores(cfg, params, statics, batch, dist)
        return jax.lax.top_k(scores, top_k)
    return serve


def build_lm_decode(cfg, dist=None, seq_axes=("model",)):
    from repro.models.transformer import decode_step

    def serve(params, cache, token):
        return decode_step(cfg, params, cache, token, dist, seq_axes=seq_axes)
    return serve


def build_lm_prefill(cfg, dist=None):
    from repro.models.transformer import prefill

    def serve(params, tokens):
        return prefill(cfg, params, tokens, dist)
    return serve


# ---------------------------------------------------------------------------
# request micro-batcher (the online-inference half of the paper's Fig. 4)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    rid: int
    features: dict
    t_arrival: float = dataclasses.field(default_factory=time.monotonic)


class MicroBatcher:
    """Collects requests into fixed-size batches (pad the tail) so the jitted
    serve step sees one static shape; tracks per-request latency.

    ``observer`` is the workload-telemetry tap (repro.workload): called as
    ``observer(feats, n_real)`` on every assembled batch, where ``n_real`` is
    the count of genuine (non-pad) requests — pad rows replicate a prototype
    request and must not be counted as traffic.
    """

    def __init__(self, batch_size: int, pad_request: dict,
                 observer: Callable[[dict, int], None] | None = None,
                 metrics=None):
        self.batch_size = batch_size
        self.pad_request = pad_request
        self.observer = observer
        self.queue: deque[Request] = deque()
        self.latencies: list[float] = []
        if metrics is None:
            from repro.obs import MetricRegistry
            metrics = MetricRegistry()
        self._m_requests = metrics.counter("serve.requests_total",
                                           "completed (non-pad) requests")
        self._m_latency = metrics.histogram(
            "serve.request_latency_ms", "arrival -> completion per request")

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def ready(self) -> bool:
        return len(self.queue) > 0

    def next_batch(self) -> tuple[list[Request], dict]:
        reqs = [self.queue.popleft()
                for _ in range(min(self.batch_size, len(self.queue)))]
        feats = {}
        n_pad = self.batch_size - len(reqs)
        for key in self.pad_request:
            rows = [r.features[key] for r in reqs]
            rows += [self.pad_request[key]] * n_pad
            feats[key] = jnp.stack([jnp.asarray(r) for r in rows])
        if self.observer is not None:
            self.observer(feats, len(reqs))
        return reqs, feats

    def complete(self, reqs: list[Request]) -> None:
        now = time.monotonic()
        for r in reqs:
            lat = now - r.t_arrival
            self.latencies.append(lat)
            self._m_latency.observe(lat * 1e3)
        self._m_requests.inc(len(reqs))

    def p99(self) -> float:
        from repro.obs import empirical_p99
        return empirical_p99(self.latencies)
