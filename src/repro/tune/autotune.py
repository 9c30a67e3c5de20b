"""The autotuner: measure every (backend, tile_b, n_slots) candidate per
call signature and record the winner in a ``DispatchCache``.

Candidate space (the three knobs the ROADMAP names):

  * backend — jnp segment-scan vs the pallas kernel (the BENCH_embedding
    batch-128 inversion is exactly a wrong static backend choice);
  * tile_b  — bags per grid step (pallas only);
  * n_slots — row-DMA pipeline depth (pallas only; kernels read it off the
    VMEM scratch shape, see ``kernels/embedding_bag._scratch``).

``smoke=True`` keeps the SAME signature suite (the cache's entry keys are
its schema — CI gates key-path parity against the committed file) but
shrinks the candidate set and repeats so the sweep runs in CI seconds.

Timings are best-of-``repeats`` wall-clock of a jitted call, the
``benchmarks/bench_embedding.py`` protocol. Off-TPU the pallas candidates
run in interpret mode — a semantics-true lower bound, which is precisely
what makes the measured (not assumed) choice land on jnp where interpret
mode loses. Every entry always carries BOTH ``jnp_us`` and ``pallas_us``
(plus ``best_us``) so smoke and full runs emit identical key sets.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import numpy as np

from repro.tune.dispatch import (CallSignature, DispatchCache, current_arch,
                                 signature)

#: (vocab, dim, batch, bag_len, n_fields) — the rectangular lookup shapes.
#: benchmarks/bench_embedding.py imports this as its CONFIGS, so the bench
#: baselines and the tuned suite cannot drift apart.
PLAIN_CONFIGS = [
    (10_000, 64, 32, 8, 1),
    (10_000, 64, 128, 8, 1),
    (50_000, 128, 64, 16, 1),
    (20_000, 32, 32, 16, 4),      # multi-field fused (B, F, L)
]

#: full sweep: jnp + pallas x {tile_b} x {n_slots}
TILE_B_CANDIDATES = (4, 8, 16)
N_SLOT_CANDIDATES = (2, 4)
#: smoke sweep: one tile, both pipeline depths — enough to exercise every
#: moving part without CI minutes
SMOKE_TILE_B = (8,)
SMOKE_N_SLOTS = (2, 4)

DEFAULT_REPEATS = 3
SMOKE_REPEATS = 2


def candidates(smoke: bool = False) -> list[tuple[str, int, int]]:
    """(backend, tile_b, n_slots) triples to measure. The jnp candidate
    carries the default tile/slots (it uses neither) so its cache entry is
    well-formed."""
    tiles = SMOKE_TILE_B if smoke else TILE_B_CANDIDATES
    slots = SMOKE_N_SLOTS if smoke else N_SLOT_CANDIDATES
    return [("jnp", 8, 2)] + [("pallas", tb, ns)
                              for tb in tiles for ns in slots]


@dataclasses.dataclass
class TuneCase:
    """One signature plus its measurement factory: ``make(backend, tile_b,
    n_slots)`` returns a zero-arg callable running one jitted lookup."""

    sig: CallSignature
    make: Callable[[str, int, int], Callable[[], object]]


def _time_best_us(fn: Callable[[], object], repeats: int) -> float:
    import jax
    jax.block_until_ready(fn())          # compile outside the timed loop
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


# ---------------------------------------------------------------------------
# case builders — deterministic inputs (seeded), one per lookup path
# ---------------------------------------------------------------------------

def plain_case(v: int, d: int, b: int, l: int, f: int,
               seed: int = 0) -> TuneCase:
    import jax
    import jax.numpy as jnp
    from repro.core.embedding import banked_embedding_bag, pack_table
    from repro.core.partitioning import non_uniform_partition

    rng = np.random.default_rng(seed)
    table = rng.standard_normal((v, d)).astype(np.float32)
    bt = pack_table(table, non_uniform_partition(rng.random(v) + 0.1, 8))
    per_field = v // f
    offs = jnp.asarray(np.arange(f) * per_field, jnp.int32) if f > 1 else None
    shape = (b, f, l) if f > 1 else (b, l)
    idx = jnp.asarray(rng.integers(-1, per_field, shape), jnp.int32)

    def make(backend, tile_b, n_slots):
        fn = jax.jit(lambda t, i: banked_embedding_bag(
            t, i, None, backend=backend, field_offsets=offs,
            tile_b=tile_b, n_slots=n_slots))
        return lambda: fn(bt, idx)

    return TuneCase(
        sig=signature("plain", vocab=v, dim=d, batch=b * f, bag_len=l,
                      n_fields=f),
        make=make)


def fused_case(v: int = 2_000, nc: int = 128, d: int = 64, b: int = 32,
               lc: int = 4, lr: int = 8, seed: int = 1) -> TuneCase:
    import jax
    import jax.numpy as jnp
    from repro.core.embedding import banked_cache_residual_bag, pack_table
    from repro.core.partitioning import (non_uniform_partition,
                                         uniform_partition)

    rng = np.random.default_rng(seed)
    table = rng.standard_normal((v, d)).astype(np.float32)
    bt = pack_table(table, non_uniform_partition(rng.random(v) + 0.1, 8))
    cbt = pack_table(rng.standard_normal((nc, d)).astype(np.float32),
                     uniform_partition(nc, 4))
    ci = jnp.asarray(rng.integers(-1, nc, (b, lc)), jnp.int32)
    ri = jnp.asarray(rng.integers(-1, v, (b, lr)), jnp.int32)

    def make(backend, tile_b, n_slots):
        fn = jax.jit(lambda t, c: banked_cache_residual_bag(
            t, c, ci, ri, None, backend=backend, tile_b=tile_b,
            n_slots=n_slots))
        return lambda: fn(bt, cbt)

    return TuneCase(
        sig=signature("fused", vocab=v, dim=d, batch=b,
                      bag_len=f"{lc}+{lr}"),
        make=make)


def csr_case(v: int = 10_000, d: int = 64, num_bags: int = 64,
             avg_len: int = 8, seed: int = 2) -> TuneCase:
    import jax
    import jax.numpy as jnp
    from repro.core.embedding import csr_embedding_bag, pack_table
    from repro.core.partitioning import non_uniform_partition

    rng = np.random.default_rng(seed)
    table = rng.standard_normal((v, d)).astype(np.float32)
    bt = pack_table(table, non_uniform_partition(rng.random(v) + 0.1, 8))
    lens = rng.integers(1, 2 * avg_len, num_bags)
    total = int(lens.sum())
    indices = jnp.asarray(rng.integers(0, v, total), jnp.int32)
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(lens)[:-1]]),
                          jnp.int32)

    def make(backend, tile_b, n_slots):
        fn = jax.jit(lambda t, i: csr_embedding_bag(
            t, i, offsets, num_bags, None, backend=backend, tile_b=tile_b,
            n_slots=n_slots))
        return lambda: fn(bt, indices)

    return TuneCase(
        sig=signature("csr", vocab=v, dim=d, batch=num_bags,
                      bag_len="ragged"),
        make=make)


def tiered_case(v: int = 2_000, d: int = 64, b: int = 32, l: int = 8,
                hot_dtype: str = "bf16", seed: int = 3) -> TuneCase:
    import jax
    import jax.numpy as jnp
    from repro.core.embedding import pack_table, tiered_embedding_bag
    from repro.core.partitioning import non_uniform_partition
    from repro.quant import QuantSpec, assign_tiers, build_tiered_table

    rng = np.random.default_rng(seed)
    table = (rng.standard_normal((v, d)) * 0.01).astype(np.float32)
    freq = rng.random(v) + 0.1
    bt = pack_table(table, non_uniform_partition(freq, 8))
    # budget below the int8 width forces a mixed bf16/int8/int4 tier map
    ta = assign_tiers(freq, QuantSpec(byte_budget=0.75 * d,
                                      min_hot_rows=16), d)
    tt = build_tiered_table(bt, ta.tier_of_row)
    idx = jnp.asarray(rng.integers(-1, v, (b, l)), jnp.int32)

    def make(backend, tile_b, n_slots):
        fn = jax.jit(lambda fp, i: tiered_embedding_bag(
            fp, tt, i, None, backend=backend, tile_b=tile_b,
            n_slots=n_slots))
        return lambda: fn(bt.packed, idx)

    return TuneCase(
        sig=signature("tiered", vocab=v, dim=d, batch=b, bag_len=l,
                      tier_mix=hot_dtype),
        make=make)


def replicated_case(v: int = 2_000, d: int = 64, b: int = 32, l: int = 8,
                    k_max: int = 4, n_hot: int = 16,
                    seed: int = 4) -> TuneCase:
    import jax
    import jax.numpy as jnp
    from repro.core.embedding import pack_replicated, replicated_embedding_bag
    from repro.core.partitioning import replicated_partition

    rng = np.random.default_rng(seed)
    banks = 8
    table = (rng.standard_normal((v, d)) * 0.1).astype(np.float32)
    freq = rng.random(v) + 0.1
    freq[:n_hot] += 50.0
    copies = np.ones(v, np.int32)
    copies[:n_hot] = k_max
    cap = int(np.ceil((v + n_hot * (k_max - 1)) / banks) * 1.3)
    rplan = replicated_partition(freq, banks, copies=copies,
                                 capacity_rows=cap, k_max=k_max)
    rt = pack_replicated(table, rplan, rows_per_bank=cap)
    idx = np.full((b, l), -1, np.int32)
    for i in range(b):
        k = rng.integers(1, l + 1)
        hot = rng.random(k) < 0.5
        idx[i, :k] = np.where(hot, rng.integers(0, n_hot, k),
                              rng.integers(0, v, k))
    idx = jnp.asarray(idx)

    def make(backend, tile_b, n_slots):
        fn = jax.jit(lambda t, i: replicated_embedding_bag(
            t, i, None, backend=backend, tile_b=tile_b, n_slots=n_slots))
        return lambda: fn(rt, idx)

    return TuneCase(
        sig=signature("replicated", vocab=v, dim=d, batch=b, bag_len=l,
                      k_max=k_max),
        make=make)


def default_signature_suite() -> list[TuneCase]:
    """The committed-cache suite: every BENCH_embedding rectangular shape on
    the plain path, plus one representative case per remaining entry point.
    Smoke mode runs THIS SAME list (key-path parity is the CI gate)."""
    cases = [plain_case(*cfg) for cfg in PLAIN_CONFIGS]
    cases += [fused_case(), csr_case(), tiered_case(), replicated_case()]
    return cases


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

def tune(cases: list[TuneCase] | None = None, *, smoke: bool = False,
         repeats: int | None = None, arch: str | None = None,
         log: Callable[[str], None] = print) -> DispatchCache:
    """Sweep every candidate for every case; return the populated cache.

    The winner is strict best measured latency. Per-backend minima are
    recorded alongside (``jnp_us``/``pallas_us``) so the committed file
    carries the evidence for each choice — and so ``best_us`` can be checked
    against best-of-both by the bench's dispatched scenario.
    """
    import jax
    if cases is None:
        cases = default_signature_suite()
    if repeats is None:
        repeats = SMOKE_REPEATS if smoke else DEFAULT_REPEATS
    cand = candidates(smoke)
    meta = {
        "arch": arch or current_arch(),
        "smoke": smoke,
        "repeats": repeats,
        "n_candidates": len(cand),
    }
    cache = DispatchCache(meta=meta)
    for case in cases:
        per_backend: dict[str, float] = {}
        best = None
        for backend, tile_b, n_slots in cand:
            us = _time_best_us(case.make(backend, tile_b, n_slots), repeats)
            per_backend[backend] = min(per_backend.get(backend, us), us)
            if best is None or us < best[3]:
                best = (backend, tile_b, n_slots, us)
        backend, tile_b, n_slots, us = best
        cache.record(case.sig, backend=backend, tile_b=tile_b,
                     n_slots=n_slots,
                     timings={"best_us": round(us, 3),
                              "jnp_us": round(per_backend["jnp"], 3),
                              "pallas_us": round(per_backend["pallas"], 3)})
        log(f"tuned {case.sig.key()}: {backend} tile_b={tile_b} "
            f"n_slots={n_slots} ({us:.1f}us; jnp {per_backend['jnp']:.1f} "
            f"/ pallas {per_backend['pallas']:.1f})")
    return cache
