"""The plain reference against the program's serve step, on the CPU, at the
registry's reduced variants of both configurations."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen, reference
from bench.families import dlrm as fam

#: the CPU computes fp32 matmuls in fp32, so the program and the reference
#: differ by summation order alone: a few fp32 ulps of scores below 1.
TOL = 1e-5


def _bench_cfg(arch):
    from repro.configs import get_arch
    c = get_arch(arch).reduced
    return {"name": c.name, "family": "dlrm",
            "vocab_sizes": list(c.vocab_sizes), "embed_dim": c.embed_dim,
            "n_dense": c.n_dense, "bot_mlp": list(c.bot_mlp),
            "top_mlp": list(c.top_mlp), "multi_hot": c.multi_hot,
            "dtype": jnp.dtype(c.dtype).name,
            "emb_dtype": jnp.dtype(c.emb_dtype).name,
            "table_scale": 0.1 if c.multi_hot > 1 else 1.0}


def _served(cfg, weights, feats, backend):
    from repro.models import dlrm
    from repro.serve.serve_step import build_recsys_serve, serve_tables
    pcfg = fam.program_config(cfg)
    params, statics = dlrm.init_params(pcfg, jax.random.key(0))
    params = {"emb_packed": weights["table"], "bot": weights["bot"],
              "top": weights["top"]}
    step = jax.jit(build_recsys_serve(dlrm, pcfg, statics, backend=backend))
    return np.asarray(step(params, serve_tables(statics), feats))


@pytest.mark.parametrize("arch,backend", [
    ("updlrm-paper", "jnp"), ("updlrm-paper", "pallas"),
    ("dlrm-rm2", "jnp"), ("dlrm-rm2", "pallas")])
def test_reference_agrees_with_the_serve_step(arch, backend):
    cfg = _bench_cfg(arch)
    mix = {"mode": "closed", "batch": 32, "ring": 1,
           "bag": {"mean": 10.0, "min": 1, "max": cfg["multi_hot"]},
           "ids": {"dist": "zipf", "a": 1.1}}
    feats = gen.make_traffic(cfg, mix, 9, 1.0).ring[0]
    weights = fam.make_weights(cfg, 9)
    got = _served(cfg, weights, feats, backend)
    ref = reference.scores_in_blocks(
        weights, feats, reference.field_offsets(cfg["vocab_sizes"]),
        block=16)
    assert np.abs(got - ref).max() <= TOL
    # the comparison sees a bf16 table: the step served from it fails
    w16 = {**weights, "table": weights["table"].astype(jnp.bfloat16)
           .astype(weights["table"].dtype)}
    low = _served(cfg, w16, feats, backend)
    assert np.abs(low - ref).max() > 10 * TOL


#: a reduced configuration with one bag length per field
PER_FIELD = {"name": "per-field", "family": "dlrm",
             "vocab_sizes": [300, 5000, 7, 40], "embed_dim": 8,
             "n_dense": 13, "bot_mlp": [16, 8], "top_mlp": [16],
             "multi_hot": [3, 1, 12, 2], "dtype": "float32",
             "emb_dtype": "float32", "table_scale": 0.1}


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_reference_agrees_with_the_serve_step_per_field(backend):
    """Per-field bag lengths reach the program as ``(n, F, max L_f)`` with
    -1 pads; the step (pallas interpreted on the CPU) and the reference
    agree at ``TOL``, and a bf16 table is still told apart."""
    cfg = PER_FIELD
    mix = {"mode": "closed", "batch": 32, "ring": 1, "ids": {"a": 1.1}}
    feats = gen.make_traffic(cfg, mix, 2**31 + 9, 1.0).ring[0]
    assert feats["sparse"].shape == (32, 4, 12)
    assert fam.program_config(cfg).multi_hot == 12
    weights = fam.make_weights(cfg, 9)
    got = _served(cfg, weights, feats, backend)
    ref = reference.scores_in_blocks(
        weights, feats, reference.field_offsets(cfg["vocab_sizes"]),
        block=16)
    assert np.abs(got - ref).max() <= TOL
    w16 = {**weights, "table": weights["table"].astype(jnp.bfloat16)
           .astype(weights["table"].dtype)}
    low = _served(cfg, w16, feats, backend)
    assert np.abs(low - ref).max() > 10 * TOL


def test_pooled_skips_each_fields_padding():
    """Fields of bag lengths 1, 4 and 2 in one (N, F, 4) layout: each
    field's sum is of its own ids alone."""
    rng = np.random.default_rng(0)
    vocab, lens = [5, 9, 4], [1, 4, 2]
    table = rng.standard_normal((sum(vocab), 3)).astype(np.float32)
    off = reference.field_offsets(vocab)
    ids = np.full((6, 3, 4), -1, np.int32)
    for f, (v, length) in enumerate(zip(vocab, lens)):
        ids[:, f, :length] = rng.integers(0, v, (6, length))
    got = np.asarray(reference.pooled(jnp.asarray(table), jnp.asarray(ids),
                                      jnp.asarray(off), jnp.float32))
    want = np.zeros((6, 3, 3), np.float32)
    for f, length in enumerate(lens):
        for j in range(length):
            want[:, f] += table[ids[:, f, j] + off[f]]
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_pooled_sums_bags_and_skips_empty_positions():
    table = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)
    ids = jnp.asarray([[[0, 1, -1], [2, -1, -1]]], jnp.int32)   # (1, 2, 3)
    off = jnp.asarray([0, 3], jnp.int32)
    got = np.asarray(reference.pooled(table, ids, off, jnp.float32))
    np.testing.assert_array_equal(got, [[[2, 4], [10, 11]]])


def test_weights_are_seeded_and_in_range():
    cfg = _bench_cfg("updlrm-paper")
    a, b = fam.make_weights(cfg, 2**40 + 3), fam.make_weights(cfg, 2**40 + 3)
    c = fam.make_weights(cfg, 2**40 + 4)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a["table"], c["table"])
    t = np.asarray(a["table"])
    assert np.abs(t).max() <= cfg["table_scale"]
    assert abs(t.mean()) < 0.01 * cfg["table_scale"]
    assert t.std() == pytest.approx(cfg["table_scale"] / np.sqrt(3), rel=0.05)
