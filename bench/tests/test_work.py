"""Work counted from the shapes, and the peaks table."""
from __future__ import annotations

import json
import os

import pytest

from bench import work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_paper_batch64_is_bound_by_bytes_at_18_6_mb():
    cfg = _cfg("updlrm-paper")
    w = work.dlrm_step(cfg, batch_size=64, n_ids=64 * 8 * 256)
    assert w.bytes == pytest.approx(18.6e6, rel=0.005)
    t, bound = work.min_time(w, work.peaks("TPU v5 lite"))
    assert bound == "bytes"
    assert t == pytest.approx(18.6e6 / 819e9, rel=0.005)   # about 23 us


def test_one_hot_bulk_flops_and_bytes():
    """A one-hot configuration with a bf16 table: 26 fields of width 64."""
    cfg = {"vocab_sizes": [1000] * 26, "embed_dim": 64, "n_dense": 13,
           "bot_mlp": [512, 256, 64], "top_mlp": [512, 512, 256],
           "multi_hot": 1, "dtype": "float32", "emb_dtype": "bfloat16"}
    B = 262144
    w = work.dlrm_step(cfg, batch_size=B, n_ids=B * 26)
    # MLPs: 13-512-256-64 and 415-512-512-256-1; interaction: 351 pairs x 64
    mults = 13 * 512 + 512 * 256 + 256 * 64 \
        + 415 * 512 + 512 * 512 + 512 * 256 + 256
    assert w.flops == 2 * B * (mults + 351 * 64) + B * 26 * 64
    rows = B * 26 * 64 * 2
    assert w.bytes > 2 * rows          # rows read + pooled written
    lk = work.lookup(cfg, batch_size=B, n_ids=B * 26)
    assert lk.bytes == rows + B * 26 * 4 + rows


def test_work_counts_only_valid_ids():
    cfg = _cfg("updlrm-paper")
    full = work.lookup(cfg, batch_size=64, n_ids=64 * 8 * 256)
    half = work.lookup(cfg, batch_size=64, n_ids=64 * 8 * 128)
    assert full.bytes - half.bytes == 64 * 8 * 128 * 32 * 4


def test_per_field_bags_count_their_ids_not_the_padding():
    """One bag length per field: ``sum(L_f)`` id positions a sample, though
    the program's layout pads every field to the longest; rows only of the
    valid ids."""
    from bench import gen
    from bench.families import dlrm as fam
    cfg = {"vocab_sizes": [300, 5000, 7, 40], "embed_dim": 8, "n_dense": 13,
           "bot_mlp": [16, 8], "top_mlp": [16], "multi_hot": [3, 1, 12, 2],
           "dtype": "float32", "emb_dtype": "float32"}
    B = 64
    lk = work.lookup(cfg, batch_size=B, n_ids=B * 18)
    assert lk.flops == B * 18 * 8
    assert lk.bytes == B * 18 * 8 * 4 + B * 18 * 4 + B * 4 * 8 * 4
    mix = {"mode": "closed", "batch": B, "ring": 1, "ids": {"a": 1.1}}
    batch = gen.make_traffic(cfg, mix, 5, 1.0).ring[0]
    assert batch["sparse"].shape == (B, 4, 12)      # 48 positions, 18 ids
    assert fam.lookup_work(cfg, batch) == lk
    assert fam.step_work(cfg, batch) == work.dlrm_step(
        cfg, batch_size=B, n_ids=B * 18)
    # an integer multi_hot of the longest field's length counts every
    # position of the padded layout
    padded = work.lookup({**cfg, "multi_hot": 12}, batch_size=B,
                         n_ids=B * 18)
    assert padded.bytes - lk.bytes == B * (48 - 18) * 4


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
