"""DLRM configurations: weights from the seed, the program's serve path,
and the reference and work of one step.

The benchmark makes every weight itself, on the device, in one jitted call
from the seed (``make_weights``), in the dtypes the configuration states.
The program gets them in its own parameter layout (``System``); the
reference (``bench/reference.py``) gets them again from the same seed, after
the program's state is freed.

Values are uniform, from a counter hash of (seed, leaf, element), so a leaf
of any size is made in one fused pass with no intermediate the size of the
table: the table as U(-s, s) with ``s = table_scale``, each MLP weight as
U(-sqrt(3 / fan_in), sqrt(3 / fan_in)) and each bias as U(-0.05, 0.05).
"""
from __future__ import annotations

import functools
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, work

BIAS_SCALE = 0.05


def _fmix32(h):
    """murmur3's 32-bit finalizer: a bijection that mixes every bit."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _uniform(shape, leaf: int, key, scale: float, dtype):
    """U(-scale, scale) of ``shape`` from (key, leaf, element index)."""
    if int(np.prod(shape)) >= 1 << 32:
        raise ValueError(f"{shape}: element indices must fit 32 bits")
    idx = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    for ax in range(1, len(shape)):
        idx = idx * jnp.uint32(shape[ax]) + jax.lax.broadcasted_iota(
            jnp.uint32, shape, ax)
    h = _fmix32(idx ^ key[0])
    h = _fmix32(h + key[1] + jnp.uint32((leaf * 0x9E3779B9) & 0xFFFFFFFF))
    u = (h >> 8).astype(jnp.float32) * np.float32(2.0 ** -24)
    return ((2.0 * u - 1.0) * scale).astype(dtype)


def weight_key(seed: int) -> np.ndarray:
    """Two 32-bit words from any whole-number seed."""
    return np.random.SeedSequence([int(seed), 7]).generate_state(2).astype(
        np.uint32)


@functools.partial(jax.jit, static_argnums=0)
def _make(frozen, key):
    cfg = dict(frozen)
    dt = jnp.dtype(cfg["dtype"])
    bot, top = work.mlp_dims(cfg)
    out = {"table": _uniform((sum(cfg["vocab_sizes"]), cfg["embed_dim"]), 0,
                             key, cfg["table_scale"],
                             jnp.dtype(cfg["emb_dtype"]))}
    leaf = 1
    for name, dims in (("bot", bot), ("top", top)):
        ws, bs = [], []
        for a, b in zip(dims[:-1], dims[1:]):
            ws.append(_uniform((a, b), leaf, key, float(np.sqrt(3.0 / a)), dt))
            bs.append(_uniform((b,), leaf + 1, key, BIAS_SCALE, dt))
            leaf += 2
        out[name] = {"w": ws, "b": bs}
    return out


def _frozen(cfg: dict):
    keys = ("vocab_sizes", "embed_dim", "n_dense", "bot_mlp", "top_mlp",
            "dtype", "emb_dtype", "table_scale")
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in keys)


def make_weights(cfg: dict, seed: int) -> dict:
    """All weights of the configuration, on the device: ``table`` (the union
    of the fields' vocabularies, ``(sum(vocab), embed_dim)``), ``bot`` and
    ``top`` (``{"w": [...], "b": [...]}``)."""
    return _make(_frozen(cfg), jnp.asarray(weight_key(seed)))


def program_config(cfg: dict):
    """The program's ``DLRMConfig`` for the configuration file."""
    from repro.models.dlrm import DLRMConfig
    return DLRMConfig(
        name=cfg["name"], vocab_sizes=tuple(cfg["vocab_sizes"]),
        embed_dim=cfg["embed_dim"], n_dense=cfg["n_dense"],
        bot_mlp=tuple(cfg["bot_mlp"]), top_mlp=tuple(cfg["top_mlp"]),
        multi_hot=cfg.get("multi_hot", 1), dtype=jnp.dtype(cfg["dtype"]),
        emb_dtype=jnp.dtype(cfg["emb_dtype"]))


class System:
    """The program's serve path for one configuration and batch size.

    The parameters are laid out as ``init_params`` lays them out (its shapes
    are read with ``jax.eval_shape``), with the benchmark's weights in them,
    and the remap vectors come from the program's own placement
    (``uniform_partition`` over one bank, as ``init_params`` uses by
    default, which keeps rows in order). ``init_params`` itself is not run: it makes its random table
    in fp32 on the device, then scales it, which needs 16.1 GiB at
    dlrm-rm2's size and cannot run on one v5e. The step is
    ``jax.jit(build_recsys_serve(...)).lower(...).compile()`` as the serving
    CLI's plain loop builds it, with the CLI's default backend.
    """

    def __init__(self, cfg: dict, weights: dict, batch: int):
        from repro.core.partitioning import uniform_partition
        from repro.launch import serve as cli
        from repro.models import dlrm
        from repro.serve.serve_step import build_recsys_serve, serve_tables

        marks = [("start", time.monotonic())]
        backend = cli.parse_args([]).backend
        pcfg = program_config(cfg)
        plan = uniform_partition(pcfg.total_vocab, 1)
        if plan.n_banks != 1 or not np.array_equal(
                plan.slot_of_row, np.arange(pcfg.total_vocab)):
            raise ValueError("expected the program's one-bank placement to "
                             "keep rows in order")
        marks.append(("placement", time.monotonic()))
        shape0 = jax.tree.map(
            lambda a: (a.shape, a.dtype),
            jax.eval_shape(lambda k: dlrm.init_params(pcfg, k, plan=plan)[0],
                           jax.random.key(0)))
        statics = {
            "remap_bank": jnp.asarray(plan.bank_of_row, jnp.int32),
            "remap_slot": jnp.asarray(plan.slot_of_row, jnp.int32),
            "n_banks": plan.n_banks,
            "rows_per_bank": int(plan.max_rows_per_bank),
            "field_offsets": jnp.asarray(pcfg.field_offsets(), jnp.int32),
        }
        self.params = {"emb_packed": weights["table"], "bot": weights["bot"],
                       "top": weights["top"]}
        marks.append(("layout", time.monotonic()))
        got = jax.tree.map(lambda a: (a.shape, a.dtype), self.params)
        if got != shape0:
            raise ValueError(f"weights do not fit the program's layout: "
                             f"{got} != {shape0}")
        self.tables = serve_tables(statics)
        self.batch = batch
        F, L = len(cfg["vocab_sizes"]), int(cfg.get("multi_hot", 1))
        self.batch_type = {
            "dense": jax.ShapeDtypeStruct((batch, cfg["n_dense"]),
                                          jnp.float32),
            "sparse": jax.ShapeDtypeStruct(
                (batch, F) if L == 1 else (batch, F, L), jnp.int32)}
        lowered = jax.jit(build_recsys_serve(
            dlrm, pcfg, statics, backend=backend)).lower(
                self.params, self.tables, self.batch_type)
        marks.append(("lower", time.monotonic()))
        self.step = lowered.compile()
        marks.append(("compile", time.monotonic()))
        #: seconds of each stage of the build, for the run's set-up line
        self.setup_marks = [(b[0], b[1] - a[1])
                            for a, b in zip(marks, marks[1:])]
        #: the executable's name, as the profiler's trace names its runs
        self.module = re.match(r"HloModule (\S+?),",
                               self.step.as_text()).group(1)

    def __call__(self, batch):
        return self.step(self.params, self.tables, batch)

    def batcher(self, pad_request: dict):
        """The program's request queue, padding with ``pad_request``."""
        from repro.serve.serve_step import MicroBatcher
        return MicroBatcher(self.batch, pad_request)

    def free(self) -> None:
        for leaf in jax.tree.leaves((self.params, self.tables)):
            leaf.delete()
        self.params = self.tables = None


class Control(System):
    """The comparison's control: the reference at ``precision`` in the
    program's place, behind the program's own batcher."""

    def __init__(self, cfg: dict, weights: dict, batch: int, precision: str):
        self.params = {**weights, "table": reference.stored_table(
            weights["table"], precision)}
        self.tables = jnp.asarray(reference.field_offsets(cfg["vocab_sizes"]))
        self.batch, self.precision = batch, precision
        self.setup_marks, self.module = [], f"control-{precision}"

    def __call__(self, batch):
        return reference.scores_jit(self.params, batch["dense"],
                                    batch["sparse"], self.tables,
                                    self.precision)


def reference_scores(cfg: dict, seed: int, feats: dict, *, block: int,
                     precision: str = "float32") -> np.ndarray:
    """The reference's scores of ``feats`` from freshly made weights."""
    weights = make_weights(cfg, seed)
    try:
        return reference.scores_in_blocks(
            weights, feats, reference.field_offsets(cfg["vocab_sizes"]),
            block=block, precision=precision)
    finally:
        for leaf in jax.tree.leaves(weights):
            leaf.delete()


def lookup_work(cfg: dict, batch: dict) -> work.Work:
    """What the embedding-bag stage of one step over ``batch`` must do."""
    return work.lookup(cfg, batch_size=batch["dense"].shape[0],
                       n_ids=int((batch["sparse"] >= 0).sum()))


def step_work(cfg: dict, batch: dict) -> work.Work:
    """What one step over ``batch`` (host arrays) must compute and move."""
    return work.dlrm_step(cfg, batch_size=batch["dense"].shape[0],
                          n_ids=int((batch["sparse"] >= 0).sum()))
