"""What a step must compute and move, counted from the shapes.

The count is of the algorithm, not of an implementation: whichever path
the program takes (a per-call re-layout of the table, lane padding, a remap,
a full Gram matrix where half is used), the work stays the same, so a share
of the chip's peak computed from it cannot pass 100% by a change of path.

One DLRM step over ``batch_size`` samples with ``n_ids`` valid ids in all:

* FLOPs: 2 per multiply-add of the bottom and top MLPs, 2 * D per pair
  i < j of the interaction's F + 1 vectors, and D adds per id pooled;
* bytes: each valid id's row at its stored width, the ids (int32, every
  position), the dense inputs (float32), the MLP weights and biases, the
  pooled bags at the table's width, and the scores (float32).
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def min_time(w: Work, peak: dict) -> tuple[float, str]:
    """The least time the chip could take for ``w``, and which peak bounds
    it: ``"flops"`` or ``"bytes"``."""
    tf = w.flops / peak["flops_per_s"]
    tb = w.bytes / peak["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")


def _itemsize(dtype: str) -> int:
    return np.dtype({"bfloat16": "float16"}.get(dtype, dtype)).itemsize


def mlp_dims(cfg: dict):
    """Layer widths of the bottom and top MLPs, inputs first."""
    f = len(cfg["vocab_sizes"])
    bot = [cfg["n_dense"], *cfg["bot_mlp"]]
    top = [(f + 1) * f // 2 + cfg["embed_dim"], *cfg["top_mlp"], 1]
    return bot, top


def lookup(cfg: dict, *, batch_size: int, n_ids: int) -> Work:
    """The embedding-bag stage alone: read each valid id's row and the ids,
    add, write the pooled bags."""
    d = cfg["embed_dim"]
    f = len(cfg["vocab_sizes"])
    positions = batch_size * f * int(cfg.get("multi_hot", 1))
    emb = _itemsize(cfg["emb_dtype"])
    return Work(flops=float(n_ids * d),
                bytes=float(n_ids * d * emb + positions * 4
                            + batch_size * f * d * emb))


def dlrm_step(cfg: dict, *, batch_size: int, n_ids: int) -> Work:
    """One whole serve step: the lookup, the MLPs and the interaction."""
    d = cfg["embed_dim"]
    f = len(cfg["vocab_sizes"])
    bot, top = mlp_dims(cfg)
    mults = sum(a * b for a, b in zip(bot[:-1], bot[1:])) \
        + sum(a * b for a, b in zip(top[:-1], top[1:]))
    params = mults + sum(bot[1:]) + sum(top[1:])
    pairs = (f + 1) * f // 2
    dense = Work(flops=float(2 * batch_size * (mults + pairs * d)),
                 bytes=float(params * _itemsize(cfg["dtype"])
                             + batch_size * cfg["n_dense"] * 4
                             + batch_size * 4))
    return lookup(cfg, batch_size=batch_size, n_ids=n_ids) + dense
