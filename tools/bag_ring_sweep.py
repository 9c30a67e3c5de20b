#!/usr/bin/env python3
"""Time the ``updlrm_bag`` kernel at several row-copy ring depths on a TPU.

    python3 tools/bag_ring_sweep.py [--batches 64,1024] [--depths 2,4,8,16,32,64]
        [--seed N] [--reps 5] [--out FILE.json]

The table is updlrm-paper's at full width (8 x 2,360,650 rows x 32 fp32,
``bench/configs/updlrm-paper.json``), made in ``pack_lanes``'s layout; the
ids are the benchmark's GoodReads traffic (``bench/gen.py``: 256-position
bags of Zipf(1.18) ids, Poisson(245.8) lengths) for batches of requests of
8 fields, resolved to table rows as the serve path resolves them. For each
batch size and depth the kernel is compiled, its pooled bags are checked
bit for bit against depth 2's, and its device time per call is read from a
profiler trace. One JSON line per (batch, depth) goes to standard output,
and all of them to ``--out`` if given. Without a TPU it exits with code 2.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIG = os.path.join(ROOT, "bench", "configs", "updlrm-paper.json")


def request_rows(cfg: dict, batch: int, seed: int):
    """(batch * fields, L) int32 union-vocab rows, -1 padded, of ``batch``
    GoodReads requests."""
    import numpy as np

    from bench import gen
    mix = gen.load_mix("goodreads-bulk")
    rng = np.random.default_rng([seed, 1])
    feats = gen.samples(rng, cfg, mix, batch, gen.field_perms(rng, cfg))
    idx = feats["sparse"]                                  # (B, F, L)
    offs = np.concatenate([[0], np.cumsum(cfg["vocab_sizes"])[:-1]])
    rows = np.where(idx >= 0, idx + offs[None, :, None], -1)
    return rows.reshape(-1, idx.shape[-1]).astype(np.int32)


def kernel_seconds(fn, args, reps: int) -> float:
    """Mean device seconds of the Pallas kernels in one call of ``fn``."""
    import jax

    from bench.trace_reduce import Trace, find_xplane, is_kernel
    with tempfile.TemporaryDirectory(prefix="ring-sweep-") as d:
        jax.profiler.start_trace(d)
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        tr = Trace.from_file(find_xplane(d))
    t = sum(e.dur for c in tr.chips for e in tr.ops[c] if is_kernel(e))
    return t * 1e-9 / reps


def sweep(batches, depths, seed: int, reps: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.embedding_bag import packed_bag_pallas
    with open(CONFIG) as f:
        cfg = json.load(f)
    V, D = sum(cfg["vocab_sizes"]), cfg["embed_dim"]
    # the table made in the pack_lanes layout of a (V, D) one: 128 // D rows
    # to a lane row (V is a multiple of it)
    pack = 128 // D
    packed = jax.jit(lambda k: jax.random.uniform(
        k, (V // pack, 128), jnp.float32, -0.03, 0.03))(jax.random.key(seed))
    for batch in batches:
        rows = jnp.asarray(request_rows(cfg, batch, seed))
        n_ids = int((rows >= 0).sum())
        first = None
        for depth in depths:
            fn = jax.jit(lambda t, i, depth=depth: packed_bag_pallas(
                t, pack, i, n_slots=depth))
            out = np.asarray(fn(packed, rows))
            if first is None:
                first = out
            s = kernel_seconds(fn, (packed, rows), reps)
            yield {"batch": batch, "bags": int(rows.shape[0]),
                   "entries": int(rows.size), "ids": n_ids, "depth": depth,
                   "kernel_ms": s * 1e3,
                   "ns_per_entry": s * 1e9 / rows.size,
                   "equal_to_first": bool(np.array_equal(out, first))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batches", default="64,1024")
    ap.add_argument("--depths", default="2,4,8,16,32,64")
    ap.add_argument("--seed", type=int, default=2203401117)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    ints = lambda s: [int(x) for x in s.split(",")]  # noqa: E731
    dev = jax.devices()[0].device_kind
    lines = []
    for line in sweep(ints(args.batches), ints(args.depths), args.seed,
                      args.reps):
        line["device"] = dev
        print(json.dumps(line), flush=True)
        lines.append(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
