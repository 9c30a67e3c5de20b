"""Compile the main path's kernels and serve step for a described TPU v5e.

No chip is attached: the TPU compiler builds for a ``v5e:2x2`` topology
described from software, so these tests catch what interpret mode cannot —
Pallas lowering gaps, SMEM/VMEM overflows, programs that do not fit the
chip's 16 GiB of HBM. Nothing runs, so they say nothing about results or
time. The topology is described inside a fixture (only the worker that runs
this file loads the TPU library), and every test skips where it cannot be.
"""
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

HBM_BYTES = 16 * 2 ** 30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _sds(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _paper():
    from repro.configs import get_arch
    return get_arch("updlrm-paper").config


def _full_vocab_bag(one_chip, nb: int, k_max: int = 1, **kw):
    """updlrm-paper's forward kernel at full width over ``nb`` bags of 256
    ids, as (jitted function, argument types)."""
    from repro.kernels.embedding_bag import banked_embedding_bag_pallas
    cfg, S = _paper(), _sds(one_chip)
    V, D, F, L = cfg.total_vocab, cfg.embed_dim, cfg.n_sparse, cfg.multi_hot
    step = jax.jit(lambda t, b, s, o, m, i: banked_embedding_bag_pallas(
        t, b, s, o, m, i, tile_b=8, k_max=k_max, **kw))
    return step, (S((V, D), jnp.float32), S((V * k_max,), jnp.int32),
                  S((V * k_max,), jnp.int32), S((F,), jnp.int32),
                  S((1,), jnp.int32), S((nb, L), jnp.int32))


@pytest.mark.parametrize("k_max", [1, 2])
def test_banked_bag_kernel_full_vocab(one_chip, ring_depths, k_max):
    """updlrm-paper's forward kernel at full width: 8 x 2,360,650 rows x 32
    fp32, 64 requests x 8 fields = 512 bags x 256 ids, with the row-copy
    ring at its compiled depth. SMEM holds a tile of entries, not the
    vocab-sized remap or the batch's ids; VMEM the ring's slots."""
    from repro.kernels.embedding_bag import BAG_RING_DEPTH
    step, args = _full_vocab_bag(one_chip, 64 * _paper().n_sparse, k_max)
    assert ring_depths(step, *args) == [BAG_RING_DEPTH]
    compiled = step.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("requests,n_slots", [(1024, None), (64, 2),
                                              (64, 64)])
def test_bag_kernel_ring_depths_compile(one_chip, ring_depths, requests,
                                        n_slots):
    """The kernel at the bulk cell's 1,024 requests and the chosen depth,
    and at the serve cell's 64 with the two-slot ping-pong and with 64
    slots, the deepest ring swept on the chip: every ring's VMEM slots and
    DMA semaphores fit."""
    from repro.kernels.embedding_bag import BAG_RING_DEPTH
    kw = {} if n_slots is None else {"n_slots": n_slots}
    step, args = _full_vocab_bag(one_chip, requests * _paper().n_sparse,
                                 **kw)
    assert ring_depths(step, *args) == [n_slots or BAG_RING_DEPTH]
    assert "tpu_custom_call" in step.lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def serve_step_full(one_chip):
    """The jitted updlrm-paper serve step (launch/serve.py --full), built
    from shapes and compiled once for the tests below: (lowered,
    compiled)."""
    import repro.core.embedding as E
    from repro.models import dlrm
    from repro.serve.serve_step import build_recsys_serve
    cfg, S = _paper(), _sds(one_chip)
    V, B = cfg.total_vocab, 64
    params = jax.tree.map(
        lambda x: S(x.shape, x.dtype),
        jax.eval_shape(lambda k: dlrm.init_params(cfg, k)[0],
                       jax.random.key(0)))
    tables = {"remap_bank": S((V,), jnp.int32),
              "remap_slot": S((V,), jnp.int32),
              "field_offsets": S((cfg.n_sparse,), jnp.int32)}
    statics = {"n_banks": 1, "rows_per_bank": V, **tables}
    batch = {"dense": S((B, cfg.n_dense), jnp.float32),
             "sparse": S((B, cfg.n_sparse, cfg.multi_hot), jnp.int32)}
    with pytest.MonkeyPatch.context() as mp:
        # off the chip the wrappers would pick interpret mode; compile for it
        mp.setattr(E, "_default_interpret", lambda interpret: False)
        serve = jax.jit(build_recsys_serve(dlrm, cfg, statics,
                                           backend="pallas"))
        lowered = serve.lower(params, tables, batch)
    return lowered, lowered.compile()


def test_serve_step_full_width(serve_step_full):
    """It fits one chip and runs the compiled bag kernel."""
    lowered, compiled = serve_step_full
    assert "tpu_custom_call" in lowered.as_text()
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert peak < HBM_BYTES, peak


def test_serve_step_names_the_bag_kernel(serve_step_full):
    """The kernel's HLO instruction carries the ``pallas_call``'s name, so
    a profiler's ``XLA Ops`` event for it starts ``%updlrm_bag``."""
    import re
    text = serve_step_full[1].as_text()
    kernels = re.findall(r"^\s*(%\S+) = .*custom_call_target="
                         r"\"tpu_custom_call\"", text, re.M)
    assert len(kernels) == 1 and kernels[0].startswith("%updlrm_bag")


@pytest.mark.parametrize("scope", ["/lookup/relayout/", "/lookup/resolve/",
                                   "/bottom_mlp/", "/interaction/",
                                   "/top_mlp/"])
def test_serve_step_op_names_carry_scopes(serve_step_full, scope):
    import re
    op_names = re.findall(r'op_name="([^"]*)"', serve_step_full[1].as_text())
    assert any(scope in n for n in op_names), scope


def _small_kernel_cases(S):
    """The kernels off the main path, at a small vocab: they keep their
    scalar-prefetched remaps, so they must at least keep lowering. Each
    case is (function, argument types, the kernel's name)."""
    from repro.kernels import embedding_bag as K
    i32, f32 = jnp.int32, jnp.float32
    V, D, NB, L, C = 4096, 128, 64, 16, 512
    return {
        "fused_cache": (
            lambda e, c, eb, es, cb, cs, m, ci, ri: K.fused_cache_bag_pallas(
                e, c, eb, es, cb, cs, m, ci, ri),
            [S((V, D), f32), S((C, D), f32), S((V,), i32), S((V,), i32),
             S((C,), i32), S((C,), i32), S((1,), i32), S((NB, 4), i32),
             S((NB, L), i32)], "updlrm_fused_cache_bag"),
        "plain_cache": (
            lambda e, c, ci, ri: K.plain_cache_bag_pallas(e, c, ci, ri),
            [S((V, D), f32), S((C, D), f32), S((NB, 4), i32),
             S((NB, L), i32)], "updlrm_plain_cache_bag"),
        "ct_scatter": (
            lambda ct, i, b, s, o, m: K.ct_scatter_bag_pallas(
                ct, i, b, s, o, m, V, f32),
            [S((NB, D), f32), S((NB, L), i32), S((V,), i32), S((V,), i32),
             S((4,), i32), S((1,), i32)], "updlrm_ct_scatter"),
        "csr": (
            lambda t, b, s, m, ind, seg, off: K.csr_bag_pallas(
                t, b, s, m, ind, seg, off, NB),
            [S((V, D), f32), S((V,), i32), S((V,), i32), S((1,), i32),
             S((NB * L,), i32), S((NB * L,), i32), S((NB + 1,), i32)],
            "updlrm_csr_bag"),
    }


SMALL_KERNELS = ["fused_cache", "plain_cache", "ct_scatter", "csr"]


@pytest.mark.parametrize("name", SMALL_KERNELS)
def test_other_kernels_lower(one_chip, name):
    fn, args, _ = _small_kernel_cases(_sds(one_chip))[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", SMALL_KERNELS)
def test_other_kernels_carry_their_names(one_chip, name):
    fn, args, kernel = _small_kernel_cases(_sds(one_chip))[name]
    assert kernel in jax.jit(fn).lower(*args).as_text()
