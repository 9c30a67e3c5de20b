"""repro.workload: telemetry bounds, drift detection, migration parity,
balanced CSR sharding, and the early-exit fused kernel."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro.core.cache_runtime import (build_cache_table,
                                      build_cache_table_fixed, cap_cache_plan,
                                      entry_banks, rewrite_bag)
from repro.core.embedding import (BankedTable, balanced_csr_shards,
                                  banked_cache_residual_bag,
                                  banked_embedding_bag, pack_table,
                                  shard_csr_batch)
from repro.core.grace import mine_cooccurrence
from repro.core.partitioning import non_uniform_partition
from repro.workload import (AdaptiveEmbeddingRuntime, CountMinSketch,
                            DriftConfig, DriftDetector, DriftingZipfTrace,
                            ReplanConfig, Replanner, TableTelemetry,
                            TopKCounter, migrate_packed_leaves,
                            migrate_table, read_criteo_tsv, unpacked_rows,
                            write_criteo_tsv)
from repro.workload.migrate import permute_packed_rows


def zipf_ids(n_items, n_draws, a=1.1, seed=0):
    rng = np.random.default_rng(seed)
    p = np.arange(1, n_items + 1, dtype=np.float64) ** (-a)
    return rng.choice(n_items, n_draws, p=p / p.sum())


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

class TestCountMinSketch:
    def test_never_underestimates(self):
        ids = zipf_ids(5000, 50_000)
        cms = CountMinSketch(width=1024, depth=4)
        cms.update(ids)
        exact = np.bincount(ids, minlength=5000).astype(np.float64)
        est = cms.query(np.arange(5000))
        assert (est >= exact - 1e-9).all()

    def test_error_bound(self):
        """Overestimate <= eps * total with prob >= 1 - e^-depth; with
        depth=5 the failure prob is ~0.7% per query — check the MAX error
        over the vocab stays within the bound (generous determinstic run)."""
        ids = zipf_ids(2000, 100_000, seed=1)
        cms = CountMinSketch(width=2048, depth=5, seed=1)
        cms.update(ids)
        exact = np.bincount(ids, minlength=2000).astype(np.float64)
        err = cms.query(np.arange(2000)) - exact
        # all-query max err: allow 3x the single-query eps bound
        assert err.max() <= 3 * cms.epsilon * cms.total

    def test_error_bound_property(self):
        hypothesis = pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @given(seed=st.integers(0, 50), a=st.floats(0.6, 1.5),
               width=st.sampled_from([512, 1024, 4096]))
        @settings(max_examples=20, deadline=None)
        def check(seed, a, width):
            ids = zipf_ids(1000, 20_000, a=a, seed=seed)
            cms = CountMinSketch(width=width, depth=4, seed=seed)
            cms.update(ids)
            exact = np.bincount(ids, minlength=1000).astype(np.float64)
            est = cms.query(np.arange(1000))
            assert (est >= exact - 1e-9).all()           # conservative
            # mean overestimate is far inside the eps bound
            assert (est - exact).mean() <= cms.epsilon * cms.total

        check()

    def test_scale_decay(self):
        cms = CountMinSketch(width=64, depth=2)
        cms.update(np.array([3, 3, 3, 7]))
        cms.scale(0.5)
        assert cms.query(np.array([3]))[0] == pytest.approx(1.5)
        assert cms.total == pytest.approx(2.0)


class TestTopKCounter:
    def test_exact_under_budget(self):
        ids = zipf_ids(300, 10_000)
        tk = TopKCounter(budget=300)
        tk.update(ids)
        exact = np.bincount(ids, minlength=300)
        assert tk.evictions == 0
        for i, c in tk.counts.items():
            assert c == exact[i]

    def test_heavy_hitters_survive_eviction(self):
        ids = zipf_ids(2000, 50_000, a=1.3, seed=2)
        tk = TopKCounter(budget=128)
        tk.update(ids)
        exact = np.bincount(ids, minlength=2000)
        true_top10 = set(np.argsort(-exact)[:10].tolist())
        kept = set(int(i) for i in tk.topk(64).tolist())
        assert true_top10 <= kept


class TestDriftDetector:
    def _tel(self, vocab=2000, seed=0, perm=None, n=30_000):
        ids = zipf_ids(vocab, n, seed=seed)
        if perm is not None:
            ids = perm[ids]
        t = TableTelemetry(vocab, topk_budget=512, sketch_width=1024)
        t.observe(ids)
        return t

    def test_no_trigger_same_distribution(self):
        t = self._tel(seed=0)
        det = DriftDetector(t.freq_vector(), k=128, min_observations=100)
        t.observe(zipf_ids(2000, 30_000, seed=99))       # fresh same-dist draw
        rep = det.check(t)
        assert not rep.drifted

    def test_trigger_on_rotation(self):
        t = self._tel(seed=0)
        det = DriftDetector(t.freq_vector(), k=128, min_observations=100)
        perm = np.roll(np.arange(2000), 700)
        t.observe(perm[zipf_ids(2000, 60_000, seed=1)])
        rep = det.check(t)
        assert rep.drifted and rep.topk_jaccard < 0.6

    def test_holds_fire_below_min_observations(self):
        t = TableTelemetry(2000)
        t.observe(np.arange(50))
        det = DriftDetector(np.ones(2000), k=64, min_observations=10_000)
        assert not det.check(t).drifted


# ---------------------------------------------------------------------------
# trace generation / replay
# ---------------------------------------------------------------------------

class TestDriftingTrace:
    CFG = DriftConfig(n_items=3000, zipf_a=1.1, avg_bag=6,
                      rotate_every=100, rotate_frac=0.3,
                      burst_prob=0.02, burst_len=16, burst_items=8)

    def test_deterministic_replay(self):
        a = DriftingZipfTrace(self.CFG, seed=5).bags(250)
        b = DriftingZipfTrace(self.CFG, seed=5).bags(250)
        assert all((x == y).all() for x, y in zip(a, b))

    def test_random_access_matches_stream(self):
        tr1 = DriftingZipfTrace(self.CFG, seed=5)
        stream = tr1.bags(150)
        tr2 = DriftingZipfTrace(self.CFG, seed=5)
        assert (tr2.bag(149) == stream[149]).all()
        assert (tr2.bag(3) == stream[3]).all()           # out of order too

    def test_hot_set_rotates(self):
        tr = DriftingZipfTrace(self.CFG, seed=1)
        top0 = set(np.argsort(-tr.popularity(0))[:40].tolist())
        top3 = set(np.argsort(-tr.popularity(350))[:40].tolist())
        assert len(top0 & top3) < 20

    def test_rect_padding(self):
        tr = DriftingZipfTrace(self.CFG, seed=2)
        r = tr.rect(16, 5)
        assert r.shape == (16, 5) and r.dtype == np.int32
        assert ((r >= -1) & (r < self.CFG.n_items)).all()
        assert (r[:, 0] >= 0).all()                      # bags never empty

    def test_diurnal_oscillates(self):
        cfg = DriftConfig(n_items=2000, zipf_a=1.2, diurnal_period=200)
        tr = DriftingZipfTrace(cfg, seed=0)
        day = set(np.argsort(-tr.popularity(0))[:30].tolist())
        night = set(np.argsort(-tr.popularity(100))[:30].tolist())
        day2 = set(np.argsort(-tr.popularity(200))[:30].tolist())
        assert len(day & night) < 15                     # swapped audience
        assert len(day & day2) > 25                      # and back again


class TestCriteoReader:
    def test_synthesized_drifting_tsv_roundtrip(self, tmp_path):
        """write_criteo_tsv -> read_criteo_tsv replays cleanly: shapes, the
        populated/empty field split, determinism in (seed, row index)."""
        p = tmp_path / "drift.tsv"
        cfg = DriftConfig(n_items=500, zipf_a=1.2, avg_bag=1.0,
                          rotate_every=64, rotate_frac=0.3)
        write_criteo_tsv(str(p), 128, n_fields=5, vocab_per_field=500,
                         drift=cfg, seed=3)
        out = read_criteo_tsv(str(p), hash_vocab=500)
        assert out["sparse"].shape == (128, 26)
        assert (out["sparse"][:, :5] >= 0).all()
        assert (out["sparse"][:, 5:] == -1).all()        # unpopulated fields
        assert ((out["sparse"][:, :5] < 500)).all()
        p2 = tmp_path / "drift2.tsv"
        write_criteo_tsv(str(p2), 128, n_fields=5, vocab_per_field=500,
                         drift=cfg, seed=3)
        out2 = read_criteo_tsv(str(p2), hash_vocab=500)
        np.testing.assert_array_equal(out["sparse"], out2["sparse"])
        # the hot set actually rotates across the file
        top_a = set(np.unique(out["sparse"][:32, 0]).tolist())
        top_b = set(np.unique(out["sparse"][96:, 0]).tolist())
        assert top_a != top_b

    def test_roundtrip(self, tmp_path):
        rows = ["1\t" + "\t".join(str(i) for i in range(13)) + "\t"
                + "\t".join(f"{i:x}" for i in range(26)),
                "0\t" + "\t".join("" for _ in range(13)) + "\t"
                + "\t".join("" for _ in range(26))]
        p = tmp_path / "crit.tsv"
        p.write_text("\n".join(rows) + "\n")
        out = read_criteo_tsv(str(p), hash_vocab=1000)
        assert out["label"].tolist() == [1.0, 0.0]
        assert out["dense"].shape == (2, 13)
        assert out["sparse"].shape == (2, 26)
        assert (out["sparse"][0] >= 0).all()
        assert (out["sparse"][1] == -1).all()            # missing -> pad id


# ---------------------------------------------------------------------------
# migration
# ---------------------------------------------------------------------------

def _capacity_table(table, plan, cap):
    flat = (plan.bank_of_row.astype(np.int64) * cap
            + plan.slot_of_row).astype(np.int32)
    return BankedTable(
        packed=permute_packed_rows(jnp.asarray(table),
                                   np.arange(table.shape[0], dtype=np.int32),
                                   flat, plan.n_banks * cap),
        remap_bank=jnp.asarray(plan.bank_of_row, jnp.int32),
        remap_slot=jnp.asarray(plan.slot_of_row, jnp.int32),
        n_banks=plan.n_banks, rows_per_bank=cap)


class TestMigration:
    def _plans(self, V=400, banks=4, cap=None, seed=0):
        rng = np.random.default_rng(seed)
        cap = cap or (V // banks + 20)
        p_a = non_uniform_partition(rng.random(V) + 0.1, banks,
                                    capacity_rows=cap)
        p_b = non_uniform_partition(np.roll(rng.random(V) + 0.1, V // 3),
                                    banks, capacity_rows=cap)
        return p_a, p_b, cap

    def test_bit_identical_to_fresh_pack(self):
        V, D = 400, 24
        rng = np.random.default_rng(3)
        table = rng.standard_normal((V, D)).astype(np.float32)
        p_a, p_b, cap = self._plans(V)
        t_a = _capacity_table(table, p_a, cap)
        t_mig = migrate_table(t_a, p_b, rows_per_bank=cap)
        fresh = np.zeros((p_b.n_banks * cap, D), np.float32)
        fresh[p_b.bank_of_row.astype(np.int64) * cap + p_b.slot_of_row] \
            = table
        assert (np.asarray(t_mig.packed) == fresh).all()
        assert (np.asarray(t_mig.remap_bank) == p_b.bank_of_row).all()
        assert (np.asarray(t_mig.remap_slot) == p_b.slot_of_row).all()

    def test_migrated_lookup_identical_to_fresh_build(self):
        """The acceptance bar: migrated table + new remap arrays produce
        bit-identical lookups to a fresh build of the same plan."""
        V, D = 300, 16
        rng = np.random.default_rng(4)
        table = rng.standard_normal((V, D)).astype(np.float32)
        p_a, p_b, cap = self._plans(V)
        t_mig = migrate_table(_capacity_table(table, p_a, cap), p_b,
                              rows_per_bank=cap)
        t_fresh = _capacity_table(table, p_b, cap)
        idx = jnp.asarray(rng.integers(-1, V, (16, 6)), jnp.int32)
        out_mig = banked_embedding_bag(t_mig, idx, None, backend="jnp")
        out_fresh = banked_embedding_bag(t_fresh, idx, None, backend="jnp")
        assert (np.asarray(out_mig) == np.asarray(out_fresh)).all()

    def test_rowwise_state_follows_rows(self):
        V = 200
        rng = np.random.default_rng(5)
        p_a, p_b, cap = self._plans(V, seed=5)
        acc = jnp.asarray(rng.random(p_a.n_banks * cap).astype(np.float32))
        table = rng.standard_normal((V, 8)).astype(np.float32)
        t_a = _capacity_table(table, p_a, cap)
        tree = {"emb_packed": t_a.packed, "acc": acc,
                "dense": jnp.ones((3, 3))}
        out = migrate_packed_leaves(tree, t_a, p_b, rows_per_bank=cap)
        old_flat = p_a.bank_of_row.astype(np.int64) * cap + p_a.slot_of_row
        new_flat = p_b.bank_of_row.astype(np.int64) * cap + p_b.slot_of_row
        np.testing.assert_array_equal(
            np.asarray(out["acc"])[new_flat], np.asarray(acc)[old_flat])
        assert out["dense"] is tree["dense"]             # untouched leaf

    def test_vocab_mismatch_raises(self):
        p_a, p_b, cap = self._plans(100)
        t = _capacity_table(np.zeros((100, 4), np.float32), p_a, cap)
        bad = non_uniform_partition(np.ones(50), 4)
        with pytest.raises(ValueError):
            migrate_table(t, bad)

    def test_bad_exchange_raises(self):
        p_a, p_b, cap = self._plans(100)
        t = _capacity_table(np.zeros((100, 4), np.float32), p_a, cap)
        with pytest.raises(ValueError, match="exchange"):
            migrate_table(t, p_b, exchange="broadcast")

    def test_compact_exchange_sharded_parity(self):
        """Compact (n_moved, D) psum == full packed-size psum == fresh pack,
        on a 1x1 mesh here (the pipe-cleaner; the real 4x2-mesh parity runs
        in tests/dist_checks.py with forced host devices), including the
        no-move short-circuit that drops the collective entirely."""
        from repro.launch.mesh import make_mesh
        from repro.core.embedding import DistCtx
        V, D, banks = 120, 8, 1
        rng = np.random.default_rng(9)
        table = rng.standard_normal((V, D)).astype(np.float32)
        cap = V + 10
        p_a = non_uniform_partition(rng.random(V) + 0.1, banks,
                                    capacity_rows=cap)
        p_b = non_uniform_partition(np.roll(rng.random(V) + 0.1, 40), banks,
                                    capacity_rows=cap)
        t_a = _capacity_table(table, p_a, cap)
        mesh = make_mesh((1, 1), ("data", "model"))
        dist = DistCtx(mesh=mesh, dp_axes=("data",))
        fresh = np.zeros((banks * cap, D), np.float32)
        fresh[p_b.bank_of_row.astype(np.int64) * cap + p_b.slot_of_row] \
            = table
        for exchange in ("compact", "full"):
            t_mig = migrate_table(t_a, p_b, dist, rows_per_bank=cap,
                                  exchange=exchange)
            assert (np.asarray(t_mig.packed) == fresh).all(), exchange
        t_same = migrate_table(t_a, p_a, dist, rows_per_bank=cap)
        assert (np.asarray(t_same.packed) == np.asarray(t_a.packed)).all()


# ---------------------------------------------------------------------------
# replanner + runtime loop
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestAdaptiveLoop:
    def test_replans_on_drift_and_improves_balance(self):
        V, banks = 1500, 4
        cap = V // banks + 60
        rng = np.random.default_rng(0)
        table = rng.standard_normal((V, 8)).astype(np.float32)
        plan0 = non_uniform_partition(np.ones(V), banks, capacity_rows=cap)
        t0 = _capacity_table(table, plan0, cap)
        rcfg = ReplanConfig.for_vocab(V, banks, capacity_rows=cap,
                                      check_every=4)
        rt = AdaptiveEmbeddingRuntime(t0, plan0, rcfg, init_freq=np.ones(V))
        tr = DriftingZipfTrace(
            DriftConfig(n_items=V, zipf_a=1.2, avg_bag=10,
                        rotate_every=120, rotate_frac=0.35), seed=9)
        for _ in range(40):
            rt.observe_bags(tr.bags(24))
            rt.end_batch()
        assert rt.replanner.n_replans >= 1
        # the LIVE traffic is balanced under the current plan
        freq = rt.replanner.telemetry.freq_vector()
        cur = rt._realized_imbalance(rt.plan, freq)
        stale = rt._realized_imbalance(plan0, freq)
        assert cur <= stale
        # swap preserved capacity: shapes never changed
        assert rt.table.packed.shape == t0.packed.shape

    def test_hysteresis_skips_non_improving_replan(self):
        """Detector trips (hot set rotated) but the candidate plan would
        serve the recent window no better than the incumbent — the replan
        is SKIPPED, counted, and the detector is NOT rebased (a later check
        can still commit)."""
        V, banks = 400, 4
        rng = np.random.default_rng(0)
        # near-uniform traffic: a candidate beats the incumbent only by
        # sampling noise, never by 30% — the gate must hold every check
        cfg = ReplanConfig.for_vocab(V, banks, check_every=2,
                                     hysteresis=0.3)
        freq0 = np.ones(V)
        incumbent = non_uniform_partition(freq0, banks)
        rp = Replanner(cfg, V, init_freq=freq0, init_plan=incumbent)
        for _ in range(20):
            rp.observe_rows(rng.integers(0, V, 200))   # uniform: topk rotates
            update = rp.end_batch()
            assert update is None
        assert rp.last_report.drifted                  # the detector DID trip
        assert rp.n_skipped_replans >= 2               # skipped every check
        assert rp.n_replans == 0
        assert rp.current_plan is incumbent

    def test_hysteresis_commits_genuinely_better_plan(self):
        """Traffic concentrated on ONE bank's contiguous block: the greedy
        candidate spreads it, beating the incumbent by far more than the
        margin — the replan commits despite hysteresis."""
        from repro.core.partitioning import uniform_partition
        V, banks = 400, 4
        rng = np.random.default_rng(1)
        cfg = ReplanConfig.for_vocab(V, banks, check_every=2,
                                     hysteresis=0.05)
        incumbent = uniform_partition(V, banks)        # contiguous blocks
        rp = Replanner(cfg, V, init_freq=np.ones(V), init_plan=incumbent)
        for _ in range(20):
            rp.observe_rows(rng.integers(0, V // banks, 200))  # bank 0 only
            update = rp.end_batch()
            if update is not None:
                break
        assert update is not None and rp.n_replans == 1
        assert rp.current_plan is update.plan
        freq = update.freq
        assert (Replanner.projected_max_share(update.plan, freq)
                < Replanner.projected_max_share(incumbent, freq) * 0.95)

    def test_hysteresis_cache_aware_counts_absorbed_reads(self):
        """The cache-aware projection replays bags through (plan, capped
        cache): a hit costs ONE read on the ENTRY's bank — raw row share
        would score the same layout very differently."""
        from repro.core.grace import CacheEntry, CachePlan
        plan = non_uniform_partition(np.array([4.0, 3.0, 2.0, 1.0]), 2,
                                     capacity_rows=2)
        cp = CachePlan(groups=[np.array([0, 1])], benefits=np.array([2.0]),
                       entries=[CacheEntry(members=(0, 1), hits=5)],
                       entry_of_subset={(0, 1): 0})
        entry_bank = 1 - plan.bank_of_row[2]     # entry away from row 2
        fcp = cap_cache_plan(cp, np.array([entry_bank]), 2, 1)
        bags = [np.array([0, 1, 2])] * 4
        # rewrite: {0,1} -> one entry read on entry_bank, residual {2} on
        # its own bank -> two reads, one per bank -> perfectly balanced
        got = Replanner.projected_max_share_cached(plan, fcp, bags)
        assert got == pytest.approx(0.5)
        # raw row share of the same traffic is lopsided (rows 0,1 share a
        # bank under the greedy), which is exactly the miscount the cached
        # projection exists to avoid
        freq = np.zeros(4)
        np.add.at(freq, np.concatenate(bags), 1.0)
        assert Replanner.projected_max_share(plan, freq) \
            == pytest.approx(2 / 3)

    def test_hysteresis_cache_aware_tracks_installed_cache(self):
        """A committed cache-aware replan retains its capped cache plan as
        the hysteresis incumbent; the loop keeps functioning with the gate
        on (commits and skips both account)."""
        rng = np.random.default_rng(3)
        V, banks = 300, 2
        cfg = ReplanConfig.for_vocab(
            V, banks, check_every=2, partitioner="cache_aware",
            cache_rows_per_bank=4, mine_min_support=2, hysteresis=0.05)
        rp = Replanner(cfg, V, init_freq=np.ones(V),
                       init_plan=non_uniform_partition(np.ones(V), banks))
        assert rp.current_cache_fixed is None
        rp.observe_bags([np.array([1, 2, 3])] * 8)
        first = rp.force_replan()
        assert rp.current_cache_fixed is first.cache_fixed is not None
        drifted_decisions = 0
        for i in range(30):
            hot = 100 + 50 * (i // 10)           # rotating grouped hot set
            rp.observe_bags([np.array([hot, hot + 1, hot + 2]),
                             rng.integers(0, V, 4)])
            update = rp.end_batch()
            if update is not None:
                assert rp.current_cache_fixed is update.cache_fixed
            drifted_decisions = rp.n_replans + rp.n_skipped_replans
        assert drifted_decisions >= 1            # the gate actually ran

    def test_hysteresis_off_by_default(self):
        """hysteresis=0.0 reproduces PR-4 behavior: every drifted check
        replans, nothing is skipped."""
        V, banks = 400, 4
        rng = np.random.default_rng(2)
        cfg = ReplanConfig.for_vocab(V, banks, check_every=2)
        rp = Replanner(cfg, V, init_freq=np.ones(V),
                       init_plan=non_uniform_partition(np.ones(V), banks))
        for _ in range(20):
            rp.observe_rows(rng.integers(0, V, 200))
            rp.end_batch()
        assert rp.n_replans >= 1
        assert rp.n_skipped_replans == 0

    def test_cache_aware_replan_builds_cache_plan(self):
        V, banks = 600, 4
        cap = V // banks + 40
        rcfg = ReplanConfig.for_vocab(
            V, banks, capacity_rows=cap, partitioner="cache_aware",
            check_every=2, mine_min_support=2, min_observations=256)
        rp = Replanner(rcfg, V, init_freq=np.ones(V))
        tr = DriftingZipfTrace(
            DriftConfig(n_items=V, zipf_a=1.3, avg_bag=8,
                        rotate_every=60, rotate_frac=0.4), seed=2)
        update = None
        for _ in range(30):
            rp.observe_bags(tr.bags(16))
            update = rp.end_batch() or update
        assert update is not None and update.cache_plan is not None
        update.plan.validate()

    def test_rebuilt_cache_table_entries_exact(self):
        """After a cache_aware replan, every rebuilt cache entry stores the
        exact partial sum of its member rows (from the LIVE table values)."""
        V, banks, D = 600, 4, 8
        cap = V // banks + 40
        rng = np.random.default_rng(0)
        table = rng.standard_normal((V, D)).astype(np.float32)
        plan0 = non_uniform_partition(np.ones(V), banks, capacity_rows=cap)
        t0 = _capacity_table(table, plan0, cap)
        rcfg = ReplanConfig.for_vocab(
            V, banks, capacity_rows=cap, partitioner="cache_aware",
            check_every=2, mine_min_support=2, min_observations=256)
        rt = AdaptiveEmbeddingRuntime(t0, plan0, rcfg, init_freq=np.ones(V))
        tr = DriftingZipfTrace(
            DriftConfig(n_items=V, zipf_a=1.3, avg_bag=8,
                        rotate_every=60, rotate_frac=0.4), seed=2)
        event = None
        for _ in range(30):
            rt.observe_bags(tr.bags(16))
            event = rt.end_batch() or event
        assert event is not None
        ct = rt.rebuild_cache_table(event.update)
        cp = event.update.cache_plan
        assert ct is not None and cp.n_entries > 0
        cflat = (np.asarray(ct.remap_bank).astype(np.int64)
                 * ct.rows_per_bank + np.asarray(ct.remap_slot))
        packed = np.asarray(ct.packed)
        for e, entry in enumerate(cp.entries):
            want = table[list(entry.members)].sum(axis=0)
            np.testing.assert_allclose(packed[cflat[e]], want, atol=1e-5)


# ---------------------------------------------------------------------------
# cache-aware serving under the adaptive runtime: fixed-capacity GRACE swaps
# ---------------------------------------------------------------------------

class TestCacheSwap:
    V, BANKS, D, CRPB = 600, 4, 8, 16

    def _runtime(self, seed=0, **overrides):
        V, banks = self.V, self.BANKS
        cap = V // banks + 40
        rng = np.random.default_rng(seed)
        table = rng.standard_normal((V, self.D)).astype(np.float32)
        plan0 = non_uniform_partition(np.ones(V), banks, capacity_rows=cap)
        t0 = _capacity_table(table, plan0, cap)
        kw = dict(partitioner="cache_aware", check_every=2,
                  mine_min_support=2, min_observations=256,
                  cache_rows_per_bank=self.CRPB)
        kw.update(overrides)
        rcfg = ReplanConfig.for_vocab(V, banks, capacity_rows=cap, **kw)
        rt = AdaptiveEmbeddingRuntime(t0, plan0, rcfg, init_freq=np.ones(V),
                                      max_cache_per_bag=4,
                                      max_residual_per_bag=12)
        return rt, table

    def _drive_to_swap(self, rt, seed=2):
        tr = DriftingZipfTrace(
            DriftConfig(n_items=self.V, zipf_a=1.3, avg_bag=8,
                        rotate_every=60, rotate_frac=0.4), seed=seed)
        event = None
        for _ in range(30):
            rt.observe_bags(tr.bags(16))
            event = rt.end_batch() or event
        assert event is not None, "drift never tripped"
        return event, tr

    def test_swap_bit_identical_to_fresh_build(self):
        """Acceptance bar: the swapped-in cache path (migrated EMT + re-summed
        fixed-capacity cache table) is fp32-EXACT against tearing everything
        down and rebuilding from scratch at the same plan — arrays AND the
        served output of the fused lookup."""
        rt, table = self._runtime()
        event, tr = self._drive_to_swap(rt)
        assert event.cache_version is not None and event.cache_entries > 0
        # row values survived migration exactly
        rows = unpacked_rows(rt.table)
        np.testing.assert_array_equal(rows, table)
        # fresh EMT pack at the same fixed capacity
        cap = rt.table.rows_per_bank
        p = rt.plan
        fresh_emt = np.zeros_like(np.asarray(rt.table.packed))
        fresh_emt[p.bank_of_row.astype(np.int64) * cap + p.slot_of_row] = rows
        np.testing.assert_array_equal(np.asarray(rt.table.packed), fresh_emt)
        # fresh cache build from the same update
        fresh_ct = build_cache_table_fixed(rows, event.update.cache_fixed,
                                           dtype=np.float32)
        ct = rt.cache_table
        np.testing.assert_array_equal(np.asarray(ct.packed),
                                      np.asarray(fresh_ct.packed))
        np.testing.assert_array_equal(np.asarray(ct.remap_bank),
                                      np.asarray(fresh_ct.remap_bank))
        np.testing.assert_array_equal(np.asarray(ct.remap_slot),
                                      np.asarray(fresh_ct.remap_slot))
        # end-to-end: serve a rewritten batch through both — bit-equal
        rb = rt.rewrite(tr.rect(8, 10)[:, None, :])
        got = banked_cache_residual_bag(
            rt.table, ct, jnp.asarray(rb.cache_idx),
            jnp.asarray(rb.residual_idx), None, backend="jnp")
        t_fresh = _capacity_table(rows, p, cap)
        want = banked_cache_residual_bag(
            t_fresh, fresh_ct, jnp.asarray(rb.cache_idx),
            jnp.asarray(rb.residual_idx), None, backend="jnp")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_inflight_batch_resolves_its_own_version(self):
        """A batch rewritten just before a swap carries OLD entry numbering;
        table_for(batch.version) must return the retired table, and serving
        with it must bit-match serving fully pre-swap."""
        rt, _ = self._runtime()
        # install a first mined plan so version 1 has live entries
        event, tr = self._drive_to_swap(rt)
        v_old = rt.rewriter.version
        rb = rt.rewrite(tr.rect(8, 10)[:, None, :])      # in-flight batch
        assert rb.version == v_old
        t_old, ct_old = rt.table, rt.cache_table
        pre = banked_cache_residual_bag(
            t_old, ct_old, jnp.asarray(rb.cache_idx),
            jnp.asarray(rb.residual_idx), None, backend="jnp")
        # force a second swap while rb is in flight
        rt.observe_bags(tr.bags(64))
        event2 = rt.apply(rt.replanner.force_replan())
        assert rt.rewriter.version == v_old + 1
        assert rt.cache_table is not ct_old
        # the in-flight batch resolves against ITS version...
        assert rt.cache_table_for(rb.version) is ct_old
        post = banked_cache_residual_bag(
            rt.table, rt.cache_table_for(rb.version),
            jnp.asarray(rb.cache_idx), jnp.asarray(rb.residual_idx), None,
            backend="jnp")
        # ...and the served output is unchanged by the swap (fp32 exact:
        # migration preserves row values bit-wise)
        np.testing.assert_array_equal(np.asarray(pre), np.asarray(post))
        # a batch rewritten AFTER the swap is tagged with the new version
        rb2 = rt.rewrite(tr.rect(4, 10)[:, None, :])
        assert rb2.version == v_old + 1

    def test_retired_version_raises(self):
        rt, _ = self._runtime()
        event, tr = self._drive_to_swap(rt)
        rt.observe_bags(tr.bags(64))
        rt.apply(rt.replanner.force_replan())
        rt.observe_bags(tr.bags(64))
        rt.apply(rt.replanner.force_replan())            # retires v, v+1
        with pytest.raises(KeyError, match="retired"):
            rt.cache_table_for(0)

    def test_fixed_capacity_pad_truncate_roundtrip(self):
        """cap_cache_plan at a TIGHT capacity: kept entries keep their exact
        partial sums at in-range positions, overflow entries leave
        entry_of_subset (degrading to residual), pad positions are zero and
        unique — and the packed shape never depends on what was mined."""
        rng = np.random.default_rng(7)
        V, banks, crpb = 300, 4, 3                       # tight: 12 entries
        table = rng.standard_normal((V, 8)).astype(np.float32)
        bags = [rng.choice(40, rng.integers(2, 8)) for _ in range(400)]
        cp = mine_cooccurrence(bags, top_items=64, max_groups=32,
                               min_support=2)
        assert cp.n_entries > banks * crpb               # mining overflows
        plan = non_uniform_partition(np.ones(V) + 0.1, banks)
        fcp = cap_cache_plan(
            cp, entry_banks(cp, plan.bank_of_row, None), banks, crpb)
        cap_total = banks * crpb
        assert fcp.capacity == cap_total
        assert fcp.n_entries + fcp.n_dropped == cp.n_entries
        assert fcp.n_entries <= cap_total
        assert fcp.entry_bank.shape == (cap_total,)
        # every (bank, slot) position used exactly once, all in range
        flat = fcp.entry_bank.astype(np.int64) * crpb + fcp.entry_slot
        assert np.unique(flat).shape[0] == cap_total
        assert fcp.entry_bank.min() >= 0 and fcp.entry_bank.max() < banks
        assert fcp.entry_slot.min() >= 0 and fcp.entry_slot.max() < crpb
        # kept entries: exact sums at their positions; pads: zero
        ct = build_cache_table_fixed(table, fcp)
        packed = np.asarray(ct.packed)
        full = build_cache_table(table, fcp.plan)
        for e in range(fcp.n_entries):
            np.testing.assert_array_equal(packed[flat[e]], full[e])
        for e in range(fcp.n_entries, cap_total):
            np.testing.assert_array_equal(packed[flat[e]], 0.0)
        # capped rewrite never emits a dropped entry id
        kept_ids = set(fcp.plan.entry_of_subset.values())
        assert all(0 <= i < fcp.n_entries for i in kept_ids)
        for bag in bags[:50]:
            c, r = rewrite_bag(bag, fcp.plan)
            assert all(0 <= eid < fcp.n_entries for eid in c)
        # a roomier capacity keeps EVERYTHING (pad-only round trip)
        fcp2 = cap_cache_plan(
            cp, entry_banks(cp, plan.bank_of_row, None), banks,
            cp.n_entries)                                # >= one bank's worth
        assert fcp2.n_dropped == 0
        assert fcp2.plan.entry_of_subset == cp.entry_of_subset

    def test_residual_overflow_refuses_instead_of_dropping(self):
        """Bags longer than the residual budget must raise, not silently
        drop lookups (the budget exists for static shapes, not sampling)."""
        rt, _ = self._runtime()
        too_long = np.zeros((2, 1, 13), np.int32)        # budget is 12
        with pytest.raises(ValueError, match="residual overflow"):
            rt.rewrite(too_long)

    def test_non_cache_replan_installs_empty_plan(self):
        """A cache-enabled runtime fed a non-cache-aware update must not
        serve stale entry sums: the swap installs the empty plan."""
        rt, _ = self._runtime()
        event, tr = self._drive_to_swap(rt)
        assert rt.cache_plan.n_entries > 0
        # hand-build a plain update (no cache side)
        rt.observe_bags(tr.bags(32))
        from repro.workload import PlanUpdate
        upd = rt.replanner.force_replan()
        upd = PlanUpdate(plan=upd.plan, freq=upd.freq, report=upd.report)
        ev = rt.apply(upd)
        assert ev.cache_entries == 0
        assert rt.cache_plan.n_entries == 0
        rb = rt.rewrite(tr.rect(8, 10)[:, None, :])
        assert (rb.cache_idx == -1).all()                # pure residual


# ---------------------------------------------------------------------------
# drift checks at scale: top-K-union path == dense path on small vocabs
# ---------------------------------------------------------------------------

class TestSparseDriftCheck:
    def test_union_path_matches_dense_on_small_vocab(self):
        """With k >= vocab and every id observed (head exact), the top-K-union
        check must be NUMERICALLY IDENTICAL to the dense (vocab,) path."""
        vocab = 300
        rng = np.random.default_rng(0)
        p = np.arange(1, vocab + 1, dtype=np.float64) ** -1.1
        p /= p.sum()
        t = TableTelemetry(vocab, topk_budget=vocab)
        t.observe(np.arange(vocab))                      # all ids seen
        t.observe(rng.choice(vocab, 20_000, p=p))
        ref = t.freq_vector()
        t.observe(np.roll(np.arange(vocab), 100)[
            rng.choice(vocab, 30_000, p=p)])
        dense = DriftDetector(ref, k=vocab, min_observations=10)
        sparse = DriftDetector(ref, k=vocab, min_observations=10,
                               sparse_above=0)           # force union path
        rd, rs = dense.check(t), sparse.check(t)
        assert rd.topk_jaccard == rs.topk_jaccard
        assert rd.weighted_l1 == pytest.approx(rs.weighted_l1, abs=1e-12)
        assert rd.drifted == rs.drifted

    def test_union_path_trips_on_rotation_small_k(self):
        vocab = 300
        rng = np.random.default_rng(1)
        p = np.arange(1, vocab + 1, dtype=np.float64) ** -1.2
        p /= p.sum()
        t = TableTelemetry(vocab, topk_budget=vocab)
        t.observe(rng.choice(vocab, 20_000, p=p))
        det = DriftDetector(t.freq_vector(), k=64, min_observations=10,
                            sparse_above=0)
        assert not det.check(t).drifted                  # no drift yet
        t.observe(np.roll(np.arange(vocab), 150)[
            rng.choice(vocab, 60_000, p=p)])
        rep = det.check(t)
        assert rep.drifted and rep.topk_jaccard < 0.5

    def test_union_path_survives_out_of_range_ids(self):
        """A corrupt log row can land an id >= vocab in the head counter;
        the union check must drop it (freq_vector's keep-guard) and keep
        checking, not die with IndexError forever after."""
        vocab = 100
        t = TableTelemetry(vocab, topk_budget=64)
        t.observe(np.arange(vocab))
        t.observe(np.full(500, vocab + 7))               # corrupt hot id
        det = DriftDetector(np.ones(vocab), k=32, min_observations=10,
                            sparse_above=0)
        rep = det.check(t)                               # must not raise
        assert 0.0 <= rep.topk_jaccard <= 1.0

    def test_freq_on_matches_freq_vector(self):
        vocab = 200
        rng = np.random.default_rng(2)
        t = TableTelemetry(vocab, topk_budget=32)        # force head eviction
        t.observe(rng.integers(0, vocab, 5000))
        ids = rng.integers(0, vocab, 64)
        np.testing.assert_array_equal(t.freq_on(ids), t.freq_vector()[ids])


# ---------------------------------------------------------------------------
# balanced CSR sharding (host-side splitter; the mesh path runs in
# tests/dist_checks.py)
# ---------------------------------------------------------------------------

class TestBalancedCsrSplit:
    def test_equal_totals(self):
        rng = np.random.default_rng(0)
        lens = rng.integers(1, 40, 200)
        offsets = np.concatenate([[0], np.cumsum(lens)])
        bounds = balanced_csr_shards(offsets, 8)
        totals = offsets[bounds[1:]] - offsets[bounds[:-1]]
        assert bounds[0] == 0 and bounds[-1] == 200
        assert (np.diff(bounds) >= 0).all()
        # each shard within one max-bag of the ideal share
        ideal = offsets[-1] / 8
        assert (np.abs(totals - ideal) <= lens.max()).all()

    def test_beats_equal_bag_count_split(self):
        """Skewed raggedness: totals-based cuts are tighter than bag-count
        cuts (the whole point vs replicating / naive splitting)."""
        rng = np.random.default_rng(1)
        lens = np.where(rng.random(160) < 0.1,
                        rng.integers(50, 100, 160), rng.integers(1, 4, 160))
        offsets = np.concatenate([[0], np.cumsum(lens)])
        bounds = balanced_csr_shards(offsets, 4)
        totals = offsets[bounds[1:]] - offsets[bounds[:-1]]
        naive = np.array([offsets[40] - offsets[0], offsets[80] - offsets[40],
                          offsets[120] - offsets[80],
                          offsets[160] - offsets[120]])
        assert totals.max() <= naive.max()

    def test_shard_csr_batch_covers_every_entry(self):
        rng = np.random.default_rng(2)
        lens = rng.integers(1, 9, 37)
        offsets = np.concatenate([[0], np.cumsum(lens)])
        indices = rng.integers(0, 500, int(offsets[-1])).astype(np.int32)
        sh = shard_csr_batch(indices, offsets, 4)
        got = sh["idx"][sh["idx"] >= 0]
        assert sorted(got.tolist()) == sorted(indices.tolist())
        seg = sh["seg"][sh["idx"] >= 0]
        assert (np.sort(np.unique(seg)) == np.arange(37)).all()

    def test_degenerate_single_shard(self):
        offsets = np.array([0, 3, 5])
        bounds = balanced_csr_shards(offsets, 1)
        assert bounds.tolist() == [0, 2]


# ---------------------------------------------------------------------------
# early-exit fused kernel (satellite): parity incl. interior -1 holes
# ---------------------------------------------------------------------------

class TestFusedEarlyExit:
    def _setup(self, seed=0):
        rng = np.random.default_rng(seed)
        V, Dc, D = 120, 10, 16
        emt = rng.standard_normal((V, D)).astype(np.float32)
        cache = rng.standard_normal((Dc, D)).astype(np.float32)
        bt = pack_table(emt, non_uniform_partition(rng.random(V) + 0.1, 2))
        cbt = pack_table(cache, non_uniform_partition(rng.random(Dc) + 0.1, 2))
        return rng, V, Dc, bt, cbt

    def test_parity_suffix_padding(self):
        rng, V, Dc, bt, cbt = self._setup()
        B, Lc, Lr = 16, 3, 7
        ci = np.full((B, Lc), -1, np.int32)
        ri = np.full((B, Lr), -1, np.int32)
        for b in range(B):
            nc, nr = rng.integers(0, Lc + 1), rng.integers(0, Lr + 1)
            ci[b, :nc] = rng.integers(0, Dc, nc)
            ri[b, :nr] = rng.integers(0, V, nr)
        got = banked_cache_residual_bag(bt, cbt, jnp.asarray(ci),
                                        jnp.asarray(ri), None,
                                        backend="pallas", interpret=True)
        want = banked_cache_residual_bag(bt, cbt, jnp.asarray(ci),
                                         jnp.asarray(ri), None, backend="jnp")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_parity_interior_holes(self):
        """-1 holes BEFORE the last valid entry must still be masked (the
        early exit trims only the trailing run)."""
        rng, V, Dc, bt, cbt = self._setup(seed=7)
        B, Lc, Lr = 8, 4, 6
        ci = rng.integers(-1, Dc, (B, Lc)).astype(np.int32)
        ri = rng.integers(-1, V, (B, Lr)).astype(np.int32)
        ri[:, -1] = -1                                   # trailing pad too
        got = banked_cache_residual_bag(bt, cbt, jnp.asarray(ci),
                                        jnp.asarray(ri), None,
                                        backend="pallas", interpret=True)
        want = banked_cache_residual_bag(bt, cbt, jnp.asarray(ci),
                                         jnp.asarray(ri), None, backend="jnp")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_effective_lengths(self):
        from repro.kernels.embedding_bag import effective_lengths
        idx = jnp.asarray([[1, -1, 2, -1, -1],
                           [-1, -1, -1, -1, -1],
                           [5, 6, 7, 8, 9]], jnp.int32)
        np.testing.assert_array_equal(np.asarray(effective_lengths(idx)),
                                      [3, 0, 5])
