"""The traffic generator: seeded, faithful to its laws, data-driven."""
from __future__ import annotations

import json

import numpy as np
import pytest

from bench import gen


def _cfg(multi_hot=16):
    return {"vocab_sizes": [300, 5000, 7], "n_dense": 13,
            "multi_hot": multi_hot}


def zipf_popularity(n_items: int, a: float, rng: np.random.Generator
                    ) -> np.ndarray:
    """The program's ``data/synthetic.py`` pmf, copied as the oracle:
    normalized Zipf over a random permutation of item ids."""
    ranks = np.arange(1, n_items + 1, dtype=np.float64)
    p = ranks ** (-a)
    p /= p.sum()
    perm = rng.permutation(n_items)
    out = np.empty(n_items)
    out[perm] = p
    return out


@pytest.mark.parametrize("mix_name,multi_hot", [
    ("goodreads-open", 16), ("goodreads-bulk", 16), ("goodreads-open", 1),
    ("goodreads-bulk", 1)])
def test_same_seed_same_inputs(mix_name, multi_hot):
    mix = gen.load_mix(mix_name)
    mix.update(batch=32, ring=2, rate_per_s=400.0)
    cfg = _cfg(multi_hot)
    a = gen.make_traffic(cfg, mix, 2**33 + 17, 0.5)
    b = gen.make_traffic(cfg, mix, 2**33 + 17, 0.5)
    c = gen.make_traffic(cfg, mix, 2**33 + 18, 0.5)
    sets = (lambda t: [t.feats] if hasattr(t, "feats") else t.ring)
    for x, y, z in zip(sets(a), sets(b), sets(c)):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
        assert not np.array_equal(x["sparse"], z["sparse"])
    if hasattr(a, "due_s"):
        np.testing.assert_array_equal(a.due_s, b.due_s)
        assert len(a.due_s) == len(c.due_s) == 200   # the count is fixed
        assert np.all(np.diff(a.due_s) >= 0) and a.due_s[-1] < 0.5


def test_ids_in_range_and_bags_clipped():
    mix = gen.load_mix("goodreads-open")
    mix["bag"]["mean"] = 14.0
    t = gen.make_traffic(_cfg(16), mix, 3, 2.0)
    sp = t.feats["sparse"]
    assert sp.dtype == np.int32 and sp.shape[1:] == (3, 16)
    for f, v in enumerate(_cfg()["vocab_sizes"]):
        ids = sp[:, f][sp[:, f] >= 0]
        assert ids.min() >= 0 and ids.max() < v
    lens = (sp >= 0).sum(-1)
    assert lens.min() >= 1 and lens.max() <= 16
    # ids fill a prefix of the bag, -1 the rest
    assert np.all(np.diff((sp >= 0).astype(int), axis=-1) <= 0)


@pytest.mark.parametrize("a", [0.9, 1.0, 1.18])
def test_zipf_ranks_follow_the_pmf(a, monkeypatch):
    """Head exact, tail continuous: the rank frequencies match the Zipf pmf
    of the program's ``zipf_popularity`` within sampling noise."""
    monkeypatch.setattr(gen, "ZIPF_HEAD", 64)
    n, draws = 4000, 400_000
    r = gen.zipf_ranks(np.random.default_rng(0), n, a, draws)
    assert r.min() >= 0 and r.max() < n
    p = np.arange(1, n + 1, dtype=np.float64) ** -a
    p /= p.sum()
    got = np.bincount(r, minlength=n) / draws
    # the head rank by rank, the tail in decades
    np.testing.assert_allclose(got[:20], p[:20], rtol=0.05)
    for lo, hi in ((64, 400), (400, 4000)):
        assert abs(got[lo:hi].sum() - p[lo:hi].sum()) < 0.01
    # the copied pmf agrees with the law the sampler draws
    pop = zipf_popularity(n, a, np.random.default_rng(1))
    np.testing.assert_allclose(np.sort(pop)[::-1], p)


def test_a_new_mix_is_found_by_name(tmp_path):
    mix = {"mode": "open", "batch": 4, "rate_per_s": 50, "profile": "clo",
           "bag": {"min": 1, "max": 8}}
    (tmp_path / "my-new-mix.json").write_text(json.dumps(mix))
    got = gen.load_mix("my-new-mix", traffic_dir=str(tmp_path))
    assert got["bag"]["mean"] == gen.TABLE1["clo"]["avg_reduction"]
    assert got["ids"]["a"] == gen.TABLE1["clo"]["zipf_a"]
    t = gen.make_traffic(_cfg(8), got, 1, 1.0)
    assert len(t.due_s) == 50 and t.feats["sparse"].shape == (50, 3, 8)
    with pytest.raises(FileNotFoundError):
        gen.load_mix("no-such-mix", traffic_dir=str(tmp_path))
