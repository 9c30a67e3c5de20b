"""Serving layer: micro-batcher semantics + LM decode/prefill consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.serve_step import MicroBatcher, Request


class TestMicroBatcher:
    def test_padding_and_latency(self):
        pad = {"x": np.zeros(3, np.float32)}
        mb = MicroBatcher(batch_size=4, pad_request=pad)
        for i in range(6):
            mb.submit(Request(rid=i, features={"x": np.full(3, i, np.float32)}))
        reqs, feats = mb.next_batch()
        assert len(reqs) == 4 and feats["x"].shape == (4, 3)
        reqs2, feats2 = mb.next_batch()
        assert len(reqs2) == 2                       # tail batch
        assert feats2["x"].shape == (4, 3)           # padded to static shape
        np.testing.assert_allclose(feats2["x"][2:], 0.0)
        mb.complete(reqs)
        mb.complete(reqs2)
        assert len(mb.latencies) == 6
        assert mb.p99() >= 0.0

    def test_next_batch_spans_each_transfer_and_stack(self, host_trace):
        """One batch of B=4 over 2 keys: one ``serve.batch`` span with its
        args, 2 x 4 ``serve.h2d`` and 2 ``serve.stack`` spans inside it,
        and the same feature arrays as the rows stacked on the host."""
        rng = np.random.default_rng(0)
        pad = {"dense": np.zeros(3, np.float32),
               "sparse": np.full((2, 5), -1, np.int32)}
        rows = [{"dense": rng.standard_normal(3).astype(np.float32),
                 "sparse": rng.integers(0, 9, (2, 5)).astype(np.int32)}
                for _ in range(3)]
        mb = MicroBatcher(batch_size=4, pad_request=pad)
        for i, f in enumerate(rows):
            mb.submit(Request(rid=i, features=f))
        (reqs, feats), spans = host_trace(mb.next_batch)
        assert len(reqs) == 3
        ((b0, b1, stats),) = spans["serve.batch"]
        assert stats == {"batch": 0, "n_real": 3}
        assert len(spans["serve.h2d"]) == 8
        assert len(spans["serve.stack"]) == 2
        for s0, s1, _ in spans["serve.h2d"] + spans["serve.stack"]:
            assert b0 <= s0 <= s1 <= b1
        for key in pad:
            want = np.stack([f[key] for f in rows] + [pad[key]])
            assert feats[key].dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(feats[key]), want)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """The persistent compile cache goes where $JAX_COMPILATION_CACHE_DIR
    says, with nothing set in code, and otherwise to one fixed path in the
    checkout."""
    import os
    from repro.launch.compile_cache import ENV, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv(ENV, str(tmp_path))
    else:
        monkeypatch.delenv(ENV, raising=False)
    try:
        path = enable_compile_cache()
        if from_env:
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            root = os.path.dirname(os.path.dirname(os.path.abspath(
                __file__)))
            assert path == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


class TestDecodeConsistency:
    def test_decode_matches_prefill_next_token(self):
        """Greedy next-token from prefill == from token-by-token decode —
        the KV-cache path computes the same distribution as full attention."""
        from repro.configs import get_arch
        from repro.models import transformer as T
        cfg = get_arch("smollm-135m").reduced
        params = T.init_params(cfg, jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab)

        logits_p = T.prefill(cfg, params, toks)
        cache = T.KVCache.empty(cfg, 2, 16)
        for t in range(8):
            logits_d, cache = T.decode_step(cfg, params, cache, toks[:, t])
        np.testing.assert_allclose(
            np.asarray(logits_p[:, :cfg.vocab]),
            np.asarray(logits_d[:, :cfg.vocab]), atol=2e-2, rtol=2e-2)
        assert (jnp.argmax(logits_p, -1) == jnp.argmax(logits_d, -1)).all()
