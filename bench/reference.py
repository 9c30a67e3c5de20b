"""The plain DLRM forward that served scores are held to.

Straight ``jax.numpy`` on the benchmark's own weights (``families/dlrm.py``
makes them from the seed), importing nothing of the program:

* each field's bag is summed position by position, from the union table at
  the field's row offset, in the accumulation dtype; -1 marks an empty
  position and one-hot fields are bags of one;
* the bottom MLP (ReLU between layers, none after the last) maps the dense
  features to ``embed_dim``;
* the interaction takes the dot of every pair (i < j, row-major) of
  ``[bottom output, field 0, ..., field F-1]`` and appends the bottom
  output;
* the top MLP (ReLU between layers) gives one logit, and the score is its
  sigmoid.

This is the DLRM of arXiv:1906.00091 with one departure: the published
code orders the pairs as its lower triangle and puts the bottom output
first; this order is a fixed permutation of the top MLP's input rows, and
is the one the served model uses.

``precision="float32"`` computes in fp32 with every matmul at
``Precision.HIGHEST``. The lower precisions are the comparison's control
(see ``control.py``): ``"bfloat16"`` stores the table and weights and does
every sum and product in bf16; ``"bfloat16_table"`` stores the table alone
in bf16 and computes as ``"float32"`` does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: precision -> (table storage dtype, compute dtype, matmul precision); a
#: storage dtype of None keeps the table as the configuration stores it.
PRECISIONS = {
    "float32": (None, jnp.float32, jax.lax.Precision.HIGHEST),
    "bfloat16": (jnp.bfloat16, jnp.bfloat16, jax.lax.Precision.DEFAULT),
    "bfloat16_table": (jnp.bfloat16, jnp.float32, jax.lax.Precision.HIGHEST),
}


def field_offsets(vocab_sizes) -> np.ndarray:
    return np.concatenate([[0], np.cumsum(vocab_sizes)[:-1]]).astype(np.int32)


def pooled(table, sparse, offsets, acc_dtype):
    """(N, F, L) or (N, F) ids -> (N, F, D) bag sums, added position by
    position in ``acc_dtype``."""
    if sparse.ndim == 2:
        sparse = sparse[..., None]
    n, f, length = sparse.shape

    def add(j, acc):
        ids = sparse[:, :, j]
        valid = ids >= 0
        rows = jnp.take(table, jnp.where(valid, ids + offsets[None, :], 0),
                        axis=0).astype(acc_dtype)
        return acc + jnp.where(valid[..., None], rows, jnp.zeros((), acc_dtype))

    acc = jnp.zeros((n, f, table.shape[-1]), acc_dtype)
    return jax.lax.fori_loop(0, length, add, acc)


def mlp(layers, x, precision, dtype):
    n = len(layers["w"])
    for i, (w, b) in enumerate(zip(layers["w"], layers["b"])):
        x = jnp.matmul(x, w.astype(dtype), precision=precision,
                       preferred_element_type=dtype) + b.astype(dtype)
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def interaction(z, precision, dtype):
    """(N, F+1, D) -> (N, (F+1)F/2) dots of the pairs i < j, row-major."""
    gram = jnp.einsum("nfd,ngd->nfg", z, z, precision=precision,
                      preferred_element_type=dtype)
    iu, ju = np.triu_indices(z.shape[1], k=1)
    return gram[:, iu, ju]


def stored_table(table, precision: str):
    """The table as ``precision`` stores it."""
    store = PRECISIONS[precision][0]
    return table if store is None else table.astype(store)


def scores(weights, dense, sparse, offsets, precision: str = "float32"):
    """Scores in [0, 1] of N samples; ``weights["table"]`` is already
    ``stored_table(..., precision)``."""
    _, dtype, mm = PRECISIONS[precision]
    emb = pooled(weights["table"], sparse, offsets, dtype)
    x = mlp(weights["bot"], dense.astype(dtype), mm, dtype)
    z = jnp.concatenate([x[:, None, :], emb], axis=1)
    feat = jnp.concatenate([interaction(z, mm, dtype), x], axis=-1)
    logit = mlp(weights["top"], feat, mm, dtype)[:, 0]
    return jax.nn.sigmoid(logit.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("precision",))
def scores_jit(weights, dense, sparse, offsets, precision):
    return scores(weights, dense, sparse, offsets, precision)


def scores_in_blocks(weights, feats: dict, offsets, *, block: int,
                     precision: str = "float32") -> np.ndarray:
    """Scores of every row of ``feats`` (host arrays), ``block`` rows at a
    time so that the gathers fit beside the table; the last block is padded
    to the same shape, so one program serves all blocks."""
    dense, sparse = feats["dense"], feats["sparse"]
    n = dense.shape[0]
    off = jnp.asarray(offsets)
    weights = {**weights, "table": stored_table(weights["table"], precision)}
    out = np.empty(n, np.float32)
    for s in range(0, n, block):
        d, sp = dense[s:s + block], sparse[s:s + block]
        m = d.shape[0]
        if m < block:
            d = np.concatenate([d, np.zeros((block - m,) + d.shape[1:],
                                            d.dtype)])
            sp = np.concatenate([sp, np.full((block - m,) + sp.shape[1:],
                                             -1, sp.dtype)])
        out[s:s + m] = np.asarray(
            scores_jit(weights, d, sp, off, precision))[:m]
    return out
