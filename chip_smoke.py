#!/usr/bin/env python3
"""Serve updlrm-paper at its published size on one TPU, and check it.

    python3 chip_smoke.py

The quickest proof that the system still starts on the chip. It drives the
serving CLI's own plain loop (``python -m repro.launch.serve --arch
updlrm-paper --full``): 8 tables x 2,360,650 rows x 32 fp32, 256-hot,
batches of 64 synthetic requests from ``--seed``, with the embedding bag
running through the compiled Pallas kernel. Then it checks that:

  * the served executable contains the kernel (``tpu_custom_call``);
  * the last batch's scores are finite and of shape (64,);
  * they, and the batch's pooled bags, agree with the plain fp32
    ``jax.numpy`` scan path (``backend="jnp"``) on the same device, within
    ``SCORE_ATOL`` and ``BAG_ATOL``.

Without a TPU it exits nonzero before serving anything. The timings it
prints are a smoke test's, not a benchmark. The last line of its output is
one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "updlrm-paper"
BATCH = 64
N_BATCHES = 3
#: Both paths sum each bag in fp32 in the same entry order, so the pooled
#: bags (|x| about 1 here) should agree to a few fp32 ulps at most, and the
#: scores, sigmoids in (0, 1) computed by the same MLPs, as closely.
BAG_ATOL = 1e-6
SCORE_ATOL = 1e-6


def require(ok: bool, what: str) -> None:
    """A failed check ends the run (``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def serve_and_check(argv: list[str]) -> dict:
    """Run ``launch/serve.py`` with ``argv`` and check what it served against
    the jnp reference. Returns the measurements; raises on any failure."""
    import jax
    import numpy as np

    from repro.core.embedding import BankedTable, banked_embedding_bag
    from repro.launch import serve as cli
    from repro.serve.serve_step import build_recsys_serve

    t0 = time.perf_counter()
    out = cli.main(argv)
    serve_s = time.perf_counter() - t0

    require("tpu_custom_call" in out["lowered"].as_text(),
            "the lowered serve step holds no Pallas kernel")
    require("tpu_custom_call" in out["serve"].as_text(),
            "the compiled serve step holds no Pallas kernel")

    params, tables, batch = out["params"], out["tables"], out["batch"]
    scores = np.asarray(out["scores"])
    n = batch["dense"].shape[0]
    require(scores.shape == (n,), f"scores of shape {scores.shape}")
    require(np.isfinite(scores).all(), "non-finite scores")

    args = cli.parse_args(argv)
    spec = cli.get_arch(args.arch)
    cfg = spec.config if args.full else spec.reduced
    mod = __import__(f"repro.models.{spec.family}", fromlist=["forward"])
    statics = out["statics"]
    ref_step = jax.jit(build_recsys_serve(mod, cfg, statics, backend="jnp"))
    ref = np.asarray(ref_step(params, tables, batch))
    err = float(np.abs(scores - ref).max())

    # the pooled bags themselves, kernel against scan: the scores pass
    # through MLPs that could shrink a lookup error below their tolerance.
    # Every array is an argument: a closed-over one would be compiled into
    # the program as a 2.4 GB constant.
    table = BankedTable(params["emb_packed"], tables["remap_bank"],
                        tables["remap_slot"], statics["n_banks"],
                        statics["rows_per_bank"])

    def bags(backend):
        return jax.jit(lambda t, off, s: banked_embedding_bag(
            t, s, None, backend=backend, field_offsets=off))(
                table, tables["field_offsets"], batch["sparse"])
    got, want = np.asarray(bags("pallas")), np.asarray(bags("jnp"))
    bag_err = float(np.abs(got - want).max())
    print(f"scores vs jnp reference: max abs err {err} (limit {SCORE_ATOL}); "
          f"pooled bags: max abs err {bag_err} (limit {BAG_ATOL}), "
          f"max |bag| {float(np.abs(want).max())}")
    require(err <= SCORE_ATOL, f"scores differ from the reference by {err}")
    require(bag_err <= BAG_ATOL, f"bags differ from the scan by {bag_err}")

    walls = []
    for _ in range(N_BATCHES):
        t1 = time.perf_counter()
        jax.block_until_ready(out["serve"](params, tables, batch))
        walls.append(time.perf_counter() - t1)

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return dict(compile_s=out["compile_s"], serve_s=serve_s,
                batch_wall_s=walls,
                peak_bytes_in_use=stats.get("peak_bytes_in_use"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}; "
              "nothing was served", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {cache}")

    m = serve_and_check(["--arch", ARCH, "--full", "--backend", "auto",
                         "--batch", str(BATCH),
                         "--requests", str(BATCH * N_BATCHES),
                         "--seed", str(args.seed)])
    print(f"compile seconds (serve step): {m['compile_s']}")
    print(f"serve CLI wall seconds (init + compile + {N_BATCHES} batches): "
          f"{m['serve_s']}")
    print(f"smoke timing, not a benchmark: one batch of {BATCH} "
          f"{m['batch_wall_s']} s")
    print(f"peak_bytes_in_use: {m['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
