"""Dispatch-cache contract tests (ISSUE 9 tentpole):

key determinism, JSON persistence round-trip, miss-falls-back-to-default,
and — the load-bearing one — BIT-parity of every ``backend='tuned'``
dispatched lookup against the directly-invoked kernel at each
(backend, tile_b, n_slots) the autotuner sweep can pick, on all five
entry-point paths. Dispatched and direct runs share the exact code path
once resolved, so anything short of bitwise equality means the dispatch
layer changed the computation.
"""
import numpy as np
import pytest

from repro.tune.autotune import (candidates, csr_case, fused_case,
                                 plain_case, replicated_case, tiered_case)
from repro.tune.dispatch import (CACHE_ENV, CallSignature, Decision,
                                 DispatchCache, decide, default_cache_path,
                                 set_cache, signature)


@pytest.fixture(autouse=True)
def _reset_cache():
    """Never leak an installed cache (or pick up the repo's committed one)
    across tests: every test starts and ends with an explicit EMPTY cache."""
    set_cache(DispatchCache())
    yield
    set_cache(None)


# ---------------------------------------------------------------------------
# keys + persistence
# ---------------------------------------------------------------------------

def test_signature_key_deterministic():
    a = signature("plain", vocab=1000, dim=32, batch=16, bag_len=4)
    b = signature("plain", vocab=1000, dim=32, batch=16, bag_len="4")
    assert a == b and a.key() == b.key()
    assert a.key() == "plain|v1000|d32|b16|l4|f1|k1|tnone|bwauto"


@pytest.mark.parametrize("field,val", [
    ("path", "csr"), ("vocab", 999), ("dim", 64), ("batch", 8),
    ("bag_len", "8"), ("n_fields", 2), ("k_max", 2), ("tier_mix", "bf16"),
    ("bwd_backend", "jnp"),
])
def test_signature_key_covers_every_field(field, val):
    base = dict(path="plain", vocab=1000, dim=32, batch=16, bag_len="4",
                n_fields=1, k_max=1, tier_mix="none", bwd_backend="auto")
    changed = dict(base)
    changed[field] = val
    assert CallSignature(**base).key() != CallSignature(**changed).key()


def test_bad_path_and_bad_backend_rejected():
    with pytest.raises(ValueError):
        signature("nope", vocab=1, dim=1, batch=1, bag_len=1)
    with pytest.raises(ValueError):
        Decision(backend="auto", tile_b=8, n_slots=2)


def test_persistence_round_trip(tmp_path):
    cache = DispatchCache(meta={"arch": "test", "smoke": False,
                                "repeats": 1, "n_candidates": 3})
    for i, path in enumerate(("plain", "fused", "csr")):
        sig = signature(path, vocab=100 * (i + 1), dim=32, batch=8,
                        bag_len="ragged" if path == "csr" else 4)
        cache.record(sig, backend="pallas" if i % 2 else "jnp",
                     tile_b=4 * (i + 1), n_slots=2 + i,
                     timings={"best_us": 1.5, "jnp_us": 2.0,
                              "pallas_us": 1.5})
    out = tmp_path / "TUNE_dispatch.json"
    cache.save(str(out))
    reloaded = DispatchCache.load(str(out))
    assert reloaded.meta["version"] == cache.meta["version"]
    assert reloaded.decisions() == cache.decisions()


def test_load_rejects_schema_version_mismatch(tmp_path):
    out = tmp_path / "TUNE_dispatch.json"
    out.write_text('{"meta": {"version": 999}, "entries": {}}')
    with pytest.raises(ValueError):
        DispatchCache.load(str(out))


def test_env_var_wins_cache_path(tmp_path, monkeypatch):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path / "elsewhere.json"))
    assert default_cache_path() == str(tmp_path / "elsewhere.json")


# ---------------------------------------------------------------------------
# decide(): hit vs miss
# ---------------------------------------------------------------------------

def test_miss_falls_back_to_callers_defaults():
    cache = DispatchCache()
    set_cache(cache)
    dec = decide("plain", vocab=50, dim=8, batch=4, bag_len=2,
                 default_backend="jnp", default_tile_b=16, default_n_slots=4)
    assert dec == Decision(backend="jnp", tile_b=16, n_slots=4,
                           source="default")
    assert cache.misses == 1 and cache.hits == 0


def test_miss_leaves_an_unset_ring_depth_to_the_kernel():
    dec = decide("plain", vocab=50, dim=8, batch=4, bag_len=2,
                 default_backend="pallas", default_n_slots=None)
    assert dec == Decision(backend="pallas", tile_b=8, n_slots=None,
                           source="default")


@pytest.mark.parametrize("recorded,n_slots,want", [
    (None, None, "kernel"),     # a miss, nothing passed: the kernel's ring
    (None, 3, 3),               # a miss with the caller's depth
    (5, None, 5),               # a cache hit's depth
])
def test_tuned_lookup_reaches_the_kernel_with_its_ring_depth(
        monkeypatch, ring_depths, recorded, n_slots, want):
    """On the chip the serve path runs ``backend='tuned'`` and misses the
    cache; a depth nobody passed has to reach the compiled ``updlrm_bag``
    unset, so the kernel's own ring engages. Traced as the chip would
    trace it (pallas, compiled mode); nothing is lowered."""
    import jax
    import jax.numpy as jnp

    import repro.core.embedding as E
    import repro.tune.dispatch as D
    from repro.core.partitioning import uniform_partition
    from repro.kernels.embedding_bag import bag_ring_depth
    monkeypatch.setattr(D, "_default_backend", lambda: "pallas")
    monkeypatch.setattr(E, "_default_interpret", lambda interpret: False)
    v, d, nb, L = 64, 32, 16, 8
    cache = DispatchCache()
    if recorded is not None:
        cache.record(signature("plain", vocab=v, dim=d, batch=nb, bag_len=L),
                     backend="pallas", tile_b=8, n_slots=recorded)
    set_cache(cache)
    bt = E.pack_table(np.zeros((v, d), np.float32), uniform_partition(v, 1))
    kw = {} if n_slots is None else {"n_slots": n_slots}
    depths = ring_depths(
        lambda t, i: E.banked_embedding_bag(t, i, None, backend="tuned",
                                            **kw),
        bt, jax.ShapeDtypeStruct((nb, L), jnp.int32))
    if want == "kernel":
        want = bag_ring_depth(None, 8 * L, interpret=False)
    assert depths == [want]


def test_hit_returns_recorded_decision():
    cache = DispatchCache()
    sig = signature("plain", vocab=50, dim=8, batch=4, bag_len=2)
    cache.record(sig, backend="pallas", tile_b=4, n_slots=3)
    set_cache(cache)
    dec = decide("plain", vocab=50, dim=8, batch=4, bag_len=2,
                 default_backend="jnp", default_tile_b=16, default_n_slots=2)
    assert dec == Decision(backend="pallas", tile_b=4, n_slots=3,
                           source="cache")
    assert cache.hits == 1 and cache.misses == 0


@pytest.mark.parametrize("platform,expect_entries", [
    ("cpu-interpret", True), ("tpu-compiled", False)])
def test_cache_applies_only_on_its_platform(tmp_path, monkeypatch, platform,
                                            expect_entries):
    """A cache measured in interpret mode on the CPU must never pick a
    backend on the chip: on another platform it loads EMPTY, every lookup
    misses, and the deterministic rule applies."""
    import repro.tune.dispatch as D
    cache = DispatchCache(meta={"arch": "cpu-interpret"})
    sig = signature("plain", vocab=50, dim=8, batch=4, bag_len=2)
    cache.record(sig, backend="jnp", tile_b=4, n_slots=3)
    out = tmp_path / "TUNE_dispatch.json"
    cache.save(str(out))
    monkeypatch.setenv(CACHE_ENV, str(out))
    monkeypatch.setattr(D, "current_arch", lambda: platform)
    set_cache(None)
    dec = decide("plain", vocab=50, dim=8, batch=4, bag_len=2,
                 default_backend="pallas")
    assert (dec.source == "cache") == expect_entries
    assert dec.backend == ("jnp" if expect_entries else "pallas")


def test_near_miss_is_a_miss():
    cache = DispatchCache()
    cache.record(signature("plain", vocab=50, dim=8, batch=4, bag_len=2),
                 backend="pallas", tile_b=4, n_slots=3)
    set_cache(cache)
    dec = decide("plain", vocab=50, dim=8, batch=8, bag_len=2,  # batch differs
                 default_backend="jnp")
    assert dec.source == "default" and dec.backend == "jnp"


# ---------------------------------------------------------------------------
# bit-parity: dispatched vs direct, every sweepable candidate, all 5 paths
# ---------------------------------------------------------------------------

# small-shape TuneCases, one per entry point; each `make(backend, tile_b,
# n_slots)` builds THE production call (core/embedding.py), so running it
# with backend='tuned' exercises the real dispatch wrapper
_CASES = [
    plain_case(500, 32, 8, 4, 1, seed=10),
    plain_case(400, 16, 4, 4, 2, seed=11),          # multi-field
    fused_case(v=500, nc=32, d=32, b=8, lc=2, lr=4, seed=12),
    csr_case(v=500, d=32, num_bags=8, avg_len=4, seed=13),
    tiered_case(v=500, d=32, b=8, l=4, seed=14),
    replicated_case(v=500, d=32, b=8, l=4, k_max=2, n_hot=8, seed=15),
]


@pytest.mark.parametrize("case", _CASES, ids=lambda c: c.sig.key())
def test_dispatched_bit_matches_direct(case):
    for backend, tile_b, n_slots in candidates(smoke=False):
        direct = np.asarray(case.make(backend, tile_b, n_slots)())
        cache = DispatchCache()
        cache.record(case.sig, backend=backend, tile_b=tile_b,
                     n_slots=n_slots)
        set_cache(cache)
        # the caller's own tile/slot args are decoys: a hit must override
        tuned = np.asarray(case.make("tuned", tile_b + 3, n_slots + 1)())
        assert cache.hits >= 1, "tuned call never consulted the cache"
        assert direct.dtype == tuned.dtype and direct.shape == tuned.shape
        assert np.array_equal(direct, tuned, equal_nan=True), (
            f"dispatch changed the computation at "
            f"({backend}, tile_b={tile_b}, n_slots={n_slots})")
