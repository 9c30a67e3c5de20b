"""The comparison's control at a size a test holds: the reference in the
program's place, one precision below the configuration's, run through the
harness as a cell's run, must come out not correct under each cell's
limit."""
from __future__ import annotations

import json
import os

import pytest

from bench import control, gen, run
from conftest import ROOT

BENCH = run.load_benchmark()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
FILES = {c["name"]: c["file"] for c in BENCH["configs"]}


def control_config(name: str) -> dict:
    """The configuration with each vocabulary cut to 2,000 rows; widths,
    bag length and dtypes as published."""
    with open(os.path.join(ROOT, FILES[name])) as f:
        cfg = json.load(f)
    cfg["vocab_sizes"] = [min(v, 2000) for v in cfg["vocab_sizes"]]
    cfg["reference_block"] = 256
    return cfg


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_control_fails_the_cells_limit(cell):
    w = CELLS[cell]
    cfg = control_config(w["config"])
    mix = gen.load_mix(w["traffic"])
    if mix["mode"] == "open":
        mix["rate_per_s"] = 256.0
    else:
        mix.update(batch=256, ring=2)
    out = control.control_run(cfg, mix, run.load_limits(cell),
                              seed=2**32 + 5, seconds=1.0,
                              precision=cfg["control"], require_chip=False)
    assert out["correct"] is False
    gap = out["compared"]["score_gap"]
    assert gap["value"] > gap["limit"]
