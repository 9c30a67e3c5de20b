"""The traffic generator: seeded, faithful to its laws, data-driven."""
from __future__ import annotations

import hashlib
import json

import numpy as np
import pytest

from bench import gen
from conftest import DATA, small_config, small_mix

#: one bag length per field, as MLPerf DLRM-DCNv2 states its multi_hot_sizes
PER_FIELD = [3, 1, 12, 2]


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


def _per_field_cfg(lens=PER_FIELD):
    return {"name": "per-field", "vocab_sizes": [300, 5000, 7, 40],
            "n_dense": 13, "multi_hot": list(lens)}


def _per_field_mix(mode):
    return {"name": f"per-field-{mode}", "mode": mode, "batch": 32,
            "ring": 3, "rate_per_s": 400.0, "ids": {"a": 1.1}}


def _batches(t):
    return [t.feats] if hasattr(t, "feats") else t.ring


@pytest.mark.parametrize("mode", ["open", "closed"])
def test_each_field_holds_exactly_its_bag_length(mode):
    cfg = _per_field_cfg()
    t = gen.make_traffic(cfg, _per_field_mix(mode), 2**31 + 7, 0.5)
    for b in _batches(t):
        sp = b["sparse"]
        assert sp.dtype == np.int32
        assert sp.shape == (len(b["dense"]), 4, max(PER_FIELD))
        for f, length in enumerate(PER_FIELD):
            assert np.all(sp[:, f, :length] >= 0)
            assert np.all(sp[:, f, length:] == -1)


@pytest.mark.parametrize("mode", ["open", "closed"])
def test_per_field_ids_are_within_their_fields_vocabulary(mode):
    cfg = _per_field_cfg()
    t = gen.make_traffic(cfg, _per_field_mix(mode), 11, 2.0)
    sp = np.concatenate([b["sparse"] for b in _batches(t)])
    for f, (v, length) in enumerate(zip(cfg["vocab_sizes"], PER_FIELD)):
        ids = sp[:, f, :length]
        assert ids.min() >= 0 and ids.max() < v
    # the Zipf head shows: a field's most common id is far above 1/vocab
    big = sp[:, 1, 0]
    assert np.bincount(big).max() / len(big) > 20 / 5000


@pytest.mark.parametrize("mode", ["open", "closed"])
def test_per_field_same_seed_same_inputs(mode):
    cfg, mix = _per_field_cfg(), _per_field_mix(mode)
    a = gen.make_traffic(cfg, mix, 2**33 + 17, 0.5)
    b = gen.make_traffic(cfg, mix, 2**33 + 17, 0.5)
    c = gen.make_traffic(cfg, mix, 2**33 + 18, 0.5)
    for x, y, z in zip(_batches(a), _batches(b), _batches(c)):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
        assert not np.array_equal(x["sparse"], z["sparse"])
        assert not np.array_equal(x["dense"], z["dense"])
    if mode == "open":
        np.testing.assert_array_equal(a.due_s, b.due_s)
        assert len(a.due_s) == len(c.due_s) == 200


def test_per_field_lengths_of_one_are_one_hot():
    t = gen.make_traffic(_per_field_cfg([1, 1, 1, 1]),
                         _per_field_mix("closed"), 3, 1.0)
    sp = t.ring[0]["sparse"]
    assert sp.shape == (32, 4) and sp.min() >= 0


@pytest.mark.parametrize("bag", [
    {"bag": {"mean": 4.0, "min": 1, "max": 12}}, {"profile": "read"}],
    ids=["bag", "profile"])
def test_a_bag_law_for_a_per_field_configuration_raises(bag, tmp_path):
    """A bag law, given outright or by a Table-1 profile, contradicts the
    configuration's fixed lengths: the error names both."""
    mix = {**_per_field_mix("open"), **bag}
    mix.pop("name")
    (tmp_path / "with-a-bag.json").write_text(json.dumps(mix))
    mix = gen.load_mix("with-a-bag", traffic_dir=str(tmp_path))
    with pytest.raises(ValueError, match="'per-field'.*'with-a-bag'"):
        gen.make_traffic(_per_field_cfg(), mix, 1, 0.5)


@pytest.mark.parametrize("lens", [[3, 1, 12], [3, 0, 12, 2]],
                         ids=["short", "zero"])
def test_a_per_field_list_that_misses_a_field_raises(lens):
    with pytest.raises(ValueError, match="each of its 4 fields"):
        gen.make_traffic(_per_field_cfg(lens), _per_field_mix("closed"), 1,
                         0.5)


def test_small_config_cuts_each_fields_bag_length():
    cfg = small_config("multi-hot-small", DATA)
    with open(f"{DATA}/multi-hot-small.json") as f:
        full = json.load(f)
    assert cfg["multi_hot"] == [min(x, 16) for x in full["multi_hot"]]
    assert max(full["multi_hot"]) == 100 and max(cfg["multi_hot"]) == 16
    assert small_config("updlrm-paper")["multi_hot"] == 16


def traffic_digest(t) -> str:
    """sha256 (first 16 hex digits) of the batch size and of every array of
    the traffic, with its name, dtype and shape."""
    h = hashlib.sha256()
    arrays = ([("due_s", t.due_s)] + sorted(t.feats.items())
              if hasattr(t, "due_s") else
              [(f"{i}.{k}", v) for i, b in enumerate(t.ring)
               for k, v in sorted(b.items())])
    h.update(str(t.batch).encode())
    for name, a in arrays:
        h.update(f"{name}:{a.dtype.str}:{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


#: digests of the committed mixes' inputs, taken before the generator
#: learned per-field bag lengths: (config, mix as committed or cut by
#: ``small_mix``, seed) -> digest over 1 s of traffic
PINNED = {
    ("updlrm-paper", "goodreads-open", "committed", 2**31 + 11):
        "6e46a2663d852f95",
    ("updlrm-paper", "goodreads-open", "committed", 2**33 + 17):
        "649b56e41ec59a56",
    ("updlrm-paper", "goodreads-bulk", "committed", 2**31 + 11):
        "bacaf5c0cbb1230f",
    ("updlrm-paper", "goodreads-bulk", "committed", 2**33 + 17):
        "f53763a30eb0bb60",
    ("one-hot", "goodreads-open", "committed", 2**31 + 11):
        "c83d3fae7fa4bd51",
    ("one-hot", "goodreads-open", "committed", 2**33 + 17):
        "4f446d27dba45817",
    ("one-hot", "goodreads-bulk", "committed", 2**31 + 11):
        "d6601091478c4954",
    ("one-hot", "goodreads-bulk", "committed", 2**33 + 17):
        "e61f993b14144bd7",
    ("updlrm-paper", "goodreads-open", "small", 2**31 + 11):
        "8c1265570b08ca79",
    ("updlrm-paper", "goodreads-open", "small", 2**33 + 17):
        "1ac63084d8218c62",
    ("updlrm-paper", "goodreads-bulk", "small", 2**31 + 11):
        "4058990a8cf1357b",
    ("updlrm-paper", "goodreads-bulk", "small", 2**33 + 17):
        "509d5a8c55294039",
    ("one-hot", "goodreads-open", "small", 2**31 + 11):
        "b561f9c90230023f",
    ("one-hot", "goodreads-open", "small", 2**33 + 17):
        "64e38e152b6ceeac",
    ("one-hot", "goodreads-bulk", "small", 2**31 + 11):
        "1617c7d169060ad3",
    ("one-hot", "goodreads-bulk", "small", 2**33 + 17):
        "7f9bee36b3407c58",
}


@pytest.mark.parametrize("case", sorted(PINNED),
                         ids=lambda c: "-".join(map(str, c)))
def test_the_committed_mixes_give_the_pinned_inputs(case):
    """The committed mixes on ``small_config`` of updlrm-paper (16-hot bags)
    and of its one-hot variant give byte for byte the inputs they gave
    before per-field bag lengths existed."""
    config, mix_name, size, seed = case
    cfg = small_config("updlrm-paper")
    if config == "one-hot":
        cfg["multi_hot"] = 1
    mix = gen.load_mix(mix_name) if size == "committed" else \
        small_mix(mix_name)
    assert traffic_digest(gen.make_traffic(cfg, mix, seed, 1.0)) == \
        PINNED[case]
