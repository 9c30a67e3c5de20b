"""Pallas kernel validation: interpret-mode sweeps over shapes/dtypes against
the ref.py oracles (this container is CPU; TPU is the compile target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as K
from repro.kernels import ref as REF


@pytest.mark.parametrize("v,d,b,l", [
    (100, 16, 8, 4), (64, 100, 10, 7), (256, 64, 32, 1), (50, 33, 9, 5),
    (1000, 128, 16, 64), (16, 8, 1, 3),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_sweep(v, d, b, l, dtype):
    rng = np.random.default_rng(v + d + b + l)
    table = jnp.array(rng.standard_normal((v, d)), dtype)
    idx = jnp.array(rng.integers(-1, v, (b, l)), jnp.int32)
    got = K.embedding_bag(table, idx, interpret=True)
    want = REF.embedding_bag_ref(table, idx)
    atol = 1e-4 if dtype == jnp.float32 else 0.1
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


@pytest.mark.parametrize("v,nc,d,b,lc,lr", [
    (80, 20, 24, 12, 3, 6), (40, 5, 8, 8, 1, 1), (200, 64, 32, 16, 8, 20),
])
def test_cache_bag_sweep(v, nc, d, b, lc, lr):
    rng = np.random.default_rng(v + d)
    emt = jnp.array(rng.standard_normal((v, d)), jnp.float32)
    cache = jnp.array(rng.standard_normal((nc, d)), jnp.float32)
    ci = jnp.array(rng.integers(-1, nc, (b, lc)), jnp.int32)
    ri = jnp.array(rng.integers(-1, v, (b, lr)), jnp.int32)
    got = K.cache_bag(emt, cache, ci, ri, interpret=True)
    want = REF.cache_bag_ref(emt, cache, ci, ri)
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("b,f,d", [
    (16, 27, 64), (8, 5, 10), (128, 40, 10), (8, 2, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dot_interaction_sweep(b, f, d, dtype):
    rng = np.random.default_rng(b + f + d)
    z = jnp.array(rng.standard_normal((b, f, d)), dtype)
    got = K.dot_interaction(z, tile_b=8, interpret=True)
    want = REF.dot_interaction_ref(z)
    atol = 1e-3 if dtype == jnp.float32 else 0.5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_embedding_bag_trainable_grads():
    """custom_vjp: kernel forward, scatter-add backward == autodiff of ref."""
    rng = np.random.default_rng(5)
    table = jnp.array(rng.standard_normal((50, 16)), jnp.float32)
    idx = jnp.array(rng.integers(-1, 50, (8, 4)), jnp.int32)

    def loss_k(t):
        return (K.embedding_bag_trainable(t, idx) ** 2).sum()

    def loss_r(t):
        return (REF.embedding_bag_ref(t, idx) ** 2).sum()

    np.testing.assert_allclose(loss_k(table), loss_r(table), rtol=1e-5)
    gk = jax.grad(loss_k)(table)
    gr = jax.grad(loss_r)(table)
    np.testing.assert_allclose(gk, gr, atol=1e-4)


def test_kernel_matches_model_path():
    """kernels.dot_interaction is a drop-in for models.dlrm.dot_interaction."""
    from repro.models.dlrm import dot_interaction as model_dot
    rng = np.random.default_rng(0)
    z = jnp.array(rng.standard_normal((8, 27, 64)), jnp.float32)
    np.testing.assert_allclose(K.dot_interaction(z, tile_b=8, interpret=True),
                               model_dot(z), atol=1e-4)


def test_banked_stage2_fusion_equivalence():
    """Pallas bag over bank-masked indices == banked stage-2 partial sums."""
    from repro.core.embedding import pack_table
    from repro.core.partitioning import uniform_partition
    rng = np.random.default_rng(2)
    V, D, B, L, banks = 64, 16, 8, 6, 4
    table = rng.standard_normal((V, D)).astype(np.float32)
    plan = uniform_partition(V, banks)
    bt = pack_table(table, plan)
    idx = rng.integers(-1, V, (B, L)).astype(np.int32)
    local = np.asarray(bt.packed).reshape(banks, -1, D)
    total = np.zeros((B, D), np.float32)
    for mb in range(banks):
        # wrapper-side ownership mask -> kernel sees -1 for foreign rows
        safe = np.where(idx >= 0, idx, 0)
        mine = (idx >= 0) & (plan.bank_of_row[safe] == mb)
        local_idx = np.where(mine, plan.slot_of_row[safe], -1).astype(np.int32)
        part = K.embedding_bag(jnp.asarray(local[mb]),
                               jnp.asarray(local_idx), interpret=True)
        want = REF.banked_bag_ref(jnp.asarray(local[mb]),
                                  jnp.asarray(plan.bank_of_row),
                                  jnp.asarray(plan.slot_of_row),
                                  jnp.asarray(idx), mb)
        np.testing.assert_allclose(part, want, atol=1e-4)
        total += np.asarray(part)
    want_total = REF.embedding_bag_ref(jnp.asarray(table), jnp.asarray(idx))
    np.testing.assert_allclose(total, want_total, atol=1e-4)


def _off_chip_kernel_cases():
    """The two kernels that do not lower for a v5e yet, as (function, its
    arguments' shapes): their names are checked in the jaxpr instead of the
    compiled text (tests/test_tpu_compile.py names the others)."""
    from repro.kernels.dot_interaction import dot_interaction_pallas
    from repro.kernels.embedding_bag import tiered_embedding_bag_pallas
    i32 = jnp.int32
    V, D, NB, L = 64, 16, 8, 4
    S = jax.ShapeDtypeStruct
    return {
        "updlrm_tiered_bag": (
            lambda p, sc, t, b, s, o, m, i: tiered_embedding_bag_pallas(
                p, sc, t, b, s, o, m, i, dim=D, interpret=True),
            [S((V, 2 * D), jnp.int8), S((V,), i32), S((V,), i32),
             S((V,), i32), S((V,), i32), S((2,), i32), S((1,), i32),
             S((NB, L), i32)]),
        "updlrm_dot_interaction": (
            lambda z: dot_interaction_pallas(z, interpret=True),
            [S((NB, 4, D), jnp.float32)]),
    }


@pytest.mark.parametrize("name", ["updlrm_tiered_bag",
                                  "updlrm_dot_interaction"])
def test_off_chip_kernels_carry_their_names(name):
    fn, args = _off_chip_kernel_cases()[name]
    assert f"name={name}" in str(jax.make_jaxpr(fn)(*args))
