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
