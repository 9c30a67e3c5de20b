"""Shared helpers of the benchmark's own tests: small configurations of the
benchmark's configuration files, run on the CPU."""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: the tests' own configuration and mix files
DATA = os.path.join(HERE, "data")
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def small_config(name: str,
                 configs_dir: str = os.path.join(ROOT, "bench", "configs")
                 ) -> dict:
    """The configuration file ``name`` with its vocabularies, bag lengths and
    MLP widths cut to a size a CPU test holds; everything else as is. A bag
    length of more than 16 becomes 16: the one ``multi_hot``, or each
    field's where it gives one length per field."""
    with open(os.path.join(configs_dir, f"{name}.json")) as f:
        cfg = json.load(f)
    cfg["vocab_sizes"] = [min(v, 500) for v in cfg["vocab_sizes"]]
    cfg["bot_mlp"] = [32, cfg["embed_dim"]]
    cfg["top_mlp"] = [32]
    lens = cfg.get("multi_hot", 1)
    if isinstance(lens, list):
        cfg["multi_hot"] = [min(x, 16) for x in lens]
    elif lens > 1:
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
