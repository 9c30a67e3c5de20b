"""Shared helpers of the benchmark's own tests: small configurations of the
benchmark's configuration files, run on the CPU."""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def small_config(name: str) -> dict:
    """The configuration file ``name`` with its vocabularies, bag length and
    MLP widths cut to a size a CPU test holds; everything else as is."""
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg["vocab_sizes"] = [min(v, 500) for v in cfg["vocab_sizes"]]
    cfg["bot_mlp"] = [32, cfg["embed_dim"]]
    cfg["top_mlp"] = [32]
    if cfg.get("multi_hot", 1) > 1:
        cfg["multi_hot"] = 16
    cfg["reference_block"] = 64
    return cfg


def small_mix(name: str) -> dict:
    from bench import gen
    mix = gen.load_mix(name)
    if mix["mode"] == "open":
        mix.update(batch=8, rate_per_s=200.0)
    else:
        mix.update(batch=16, ring=2)
    if "bag" in mix:
        mix["bag"]["mean"] = 12.0
    return mix
