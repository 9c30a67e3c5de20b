"""Shape-specialized dispatch cache: the persisted half of the autotuner.

Every lookup entry point in ``core/embedding.py`` can run with
``backend='tuned'``: at TRACE time (shapes are static under jit) it builds a
``CallSignature`` from the call's static shape tuple and resolves it through
the module-level ``DispatchCache`` — a host-side dict lookup, zero runtime
cost, and deterministic per shape so the jit executable cache stays stable
(the zero-recompile serving contract is untouched).

The cache itself is ``TUNE_dispatch.json``, committed at the repo root like
the ``BENCH_*.json`` baselines and regenerated by ``launch/tune.py``. Its
shape is gate-friendly by construction (benchmarks/check_regression.py):

  * entry KEYS are the signature strings — key-path parity is the fatal
    check, so the CI smoke tune must cover the SAME signature suite as the
    committed full run (fewer candidates/repeats, never fewer signatures);
  * decision fields (``backend`` str, ``tile_b``/``n_slots`` ints) carry no
    timing marker and are NOT value-gated — they are machine-dependent and
    surface in review diffs instead;
  * measurements end in ``_us`` and ride the advisory ±50% band.

A MISS falls back to today's defaults — the caller's ``tile_b``/``n_slots``
and the pre-tuner ``auto`` backend rule (pallas on TPU, jnp elsewhere) — so
``backend='tuned'`` with no cache file behaves exactly like ``'auto'`` did.

Resolution order for the cache file: ``$REPRO_TUNE_CACHE`` > cwd > repo
root. ``set_cache()`` overrides in-process (tests, the autotuner's
self-check).
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading

CACHE_ENV = "REPRO_TUNE_CACHE"
CACHE_BASENAME = "TUNE_dispatch.json"
SCHEMA_VERSION = 1

#: lookup paths a signature can name — one per core/embedding entry point
PATHS = ("plain", "fused", "csr", "tiered", "replicated")

#: backends a cached decision may select (never "auto"/"tuned" — a decision
#: is the OUTPUT of resolution)
DECISION_BACKENDS = ("jnp", "pallas")


@dataclasses.dataclass(frozen=True)
class CallSignature:
    """The static shape tuple a dispatch decision is keyed on.

    ``bag_len`` is a string so non-rectangular paths can encode their shape
    ("4+8" for the fused cache+residual pair, "ragged" for CSR). ``batch``
    is the PRODUCT of the index leading dims (B, or B*F for multi-field
    batches) — the motivating BENCH_embedding inversion is batch-dependent,
    so it must key the decision even though tile padding absorbs it.
    ``tier_mix`` is the tiered path's static hot dtype ("none" elsewhere);
    tier COUNTS are traced values and cannot key a trace-time decision.
    """

    path: str
    vocab: int
    dim: int
    batch: int
    bag_len: str
    n_fields: int = 1
    k_max: int = 1
    tier_mix: str = "none"
    bwd_backend: str = "auto"

    def __post_init__(self):
        if self.path not in PATHS:
            raise ValueError(f"path must be one of {PATHS}, got {self.path!r}")

    def key(self) -> str:
        """Deterministic string key (the JSON entry key)."""
        return (f"{self.path}|v{self.vocab}|d{self.dim}|b{self.batch}"
                f"|l{self.bag_len}|f{self.n_fields}|k{self.k_max}"
                f"|t{self.tier_mix}|bw{self.bwd_backend}")


def signature(path: str, *, vocab: int, dim: int, batch: int, bag_len,
              n_fields: int = 1, k_max: int = 1, tier_mix: str = "none",
              bwd_backend: str = "auto") -> CallSignature:
    """Normalizing constructor: ``bag_len`` may be an int or a string."""
    return CallSignature(path=path, vocab=int(vocab), dim=int(dim),
                         batch=int(batch), bag_len=str(bag_len),
                         n_fields=int(n_fields), k_max=int(k_max),
                         tier_mix=str(tier_mix), bwd_backend=str(bwd_backend))


@dataclasses.dataclass(frozen=True)
class Decision:
    """What a resolved lookup runs with. ``source`` records whether the
    cache hit ("cache") or the deterministic default applied ("default");
    ``n_slots`` None leaves the ring depth to the kernel."""

    backend: str
    tile_b: int
    n_slots: int | None
    source: str = "default"

    def __post_init__(self):
        if self.backend not in DECISION_BACKENDS:
            raise ValueError(f"decision backend must be one of "
                             f"{DECISION_BACKENDS}, got {self.backend!r}")


class DispatchCache:
    """signature key -> decision entry, with JSON persistence.

    An entry dict holds the decision (``backend``, ``tile_b``, ``n_slots``)
    plus measurement provenance (``best_us``, ``jnp_us``, ``pallas_us``,
    ``candidates``). ``hits``/``misses`` count ``lookup`` outcomes — the
    dispatch tests use them to prove a tuned call actually consulted the
    cache rather than falling back.
    """

    def __init__(self, entries: dict | None = None,
                 meta: dict | None = None):
        self.entries: dict[str, dict] = dict(entries or {})
        self.meta: dict = dict(meta or {})
        self.meta.setdefault("version", SCHEMA_VERSION)
        self.hits = 0
        self.misses = 0

    def lookup(self, sig: CallSignature) -> Decision | None:
        e = self.entries.get(sig.key())
        if e is None:
            self.misses += 1
            return None
        self.hits += 1
        return Decision(backend=e["backend"], tile_b=int(e["tile_b"]),
                        n_slots=int(e["n_slots"]), source="cache")

    def record(self, sig: CallSignature, *, backend: str, tile_b: int,
               n_slots: int, timings: dict | None = None) -> None:
        entry = {"path": sig.path, "backend": backend,
                 "tile_b": int(tile_b), "n_slots": int(n_slots)}
        if timings:
            entry.update(timings)
        self.entries[sig.key()] = entry

    def decisions(self) -> dict[str, Decision]:
        """key -> Decision for every entry (the round-trip test surface)."""
        return {k: Decision(backend=e["backend"], tile_b=int(e["tile_b"]),
                            n_slots=int(e["n_slots"]), source="cache")
                for k, e in self.entries.items()}

    def to_doc(self) -> dict:
        return {"meta": dict(self.meta),
                "entries": {k: self.entries[k]
                            for k in sorted(self.entries)}}

    def save(self, path: str) -> None:
        with open(path, "w") as fh:
            json.dump(self.to_doc(), fh, indent=2)
            fh.write("\n")

    @classmethod
    def from_doc(cls, doc: dict) -> "DispatchCache":
        ver = doc.get("meta", {}).get("version")
        if ver != SCHEMA_VERSION:
            raise ValueError(f"dispatch cache schema version {ver!r} != "
                             f"supported {SCHEMA_VERSION} — regenerate with "
                             f"launch/tune.py")
        return cls(entries=doc.get("entries", {}), meta=doc.get("meta", {}))

    @classmethod
    def load(cls, path: str) -> "DispatchCache":
        with open(path) as fh:
            return cls.from_doc(json.load(fh))


# ---------------------------------------------------------------------------
# module-level cache: lazy-loaded once, overridable for tests
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_CACHE: DispatchCache | None = None
_LOADED = False


def _repo_root() -> str:
    # src/repro/tune/dispatch.py -> repo root is three dirs up from src/
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(os.path.dirname(here)))


def default_cache_path() -> str | None:
    """$REPRO_TUNE_CACHE (taken verbatim, even if absent — it is explicit),
    else the first existing TUNE_dispatch.json in (cwd, repo root)."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    for base in (os.getcwd(), _repo_root()):
        p = os.path.join(base, CACHE_BASENAME)
        if os.path.exists(p):
            return p
    return None


def current_arch() -> str:
    """The platform a sweep measures on and a lookup runs on, as recorded in
    ``meta.arch``: ``tpu-compiled`` on a TPU, ``<backend>-interpret``
    elsewhere (off-TPU the kernels run in interpret mode)."""
    import jax
    backend = jax.default_backend()
    return f"{backend}-{'compiled' if backend == 'tpu' else 'interpret'}"


def get_cache() -> DispatchCache:
    """The process-wide cache, loaded lazily from ``default_cache_path()``.
    No file, an unreadable one, or one measured on another platform
    (``meta.arch`` != ``current_arch()``) yields an EMPTY cache: every
    lookup misses and the deterministic defaults apply — 'tuned' degrades
    to exactly the pre-tuner behavior rather than failing, and interpret-
    mode timings never pick a backend on the chip."""
    global _CACHE, _LOADED
    with _LOCK:
        if not _LOADED:
            path = default_cache_path()
            cache = None
            if path and os.path.exists(path):
                try:
                    cache = DispatchCache.load(path)
                except (OSError, ValueError, KeyError, json.JSONDecodeError):
                    cache = None
            if cache is not None and cache.meta.get("arch") != current_arch():
                cache = None
            _CACHE = cache if cache is not None else DispatchCache()
            _LOADED = True
        return _CACHE


def set_cache(cache: DispatchCache | None) -> None:
    """Install a cache in-process (tests / the autotuner's self-check).
    ``None`` resets to lazy-load-on-next-use."""
    global _CACHE, _LOADED
    with _LOCK:
        _CACHE = cache
        _LOADED = cache is not None


def _default_backend() -> str:
    """The pre-tuner 'auto' rule — the deterministic miss fallback."""
    import jax
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def decide(path: str, *, vocab: int, dim: int, batch: int, bag_len,
           n_fields: int = 1, k_max: int = 1, tier_mix: str = "none",
           bwd_backend: str = "auto", default_backend: str | None = None,
           default_tile_b: int = 8,
           default_n_slots: int | None = 2) -> Decision:
    """Resolve one call signature: the cached decision on a hit, today's
    defaults (caller's tile_b/n_slots + the pre-tuner auto backend) on a
    miss. A ``default_n_slots`` of None stays None, so the kernel picks its
    own depth. Pure host-side; called at trace time by ``core/embedding``."""
    sig = signature(path, vocab=vocab, dim=dim, batch=batch, bag_len=bag_len,
                    n_fields=n_fields, k_max=k_max, tier_mix=tier_mix,
                    bwd_backend=bwd_backend)
    dec = get_cache().lookup(sig)
    if dec is not None:
        return dec
    return Decision(backend=default_backend or _default_backend(),
                    tile_b=int(default_tile_b),
                    n_slots=None if default_n_slots is None
                    else int(default_n_slots),
                    source="default")
