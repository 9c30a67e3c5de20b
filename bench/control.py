#!/usr/bin/env python3
"""The comparison's control: the reference in the program's place, computed
one precision below what the configuration states, run as a cell's run.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 \
        [--precisions bfloat16 bfloat16_table]

For each seed and precision (by default the configuration's ``control``;
``reference.PRECISIONS`` names the others) it runs the cell once through
``run.run_cell`` with the family's ``Control`` standing in for the program's
serve step, and prints the run's ``correct`` and the numbers it compared
beside their limits. A control that comes out correct means the comparison
cannot tell that precision from the program's. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def control_run(cfg: dict, mix: dict, limits: dict, *, seed: int,
                seconds: float, precision: str, **kw) -> dict:
    """One run of the cell with the reference at ``precision`` as the
    system under test; ``kw`` goes to ``run.run_cell``."""
    from bench import run
    fam = run.family(cfg["family"])
    return run.run_cell(
        cfg, mix, limits, seed=seed, seconds=seconds, trace=False,
        t_start=time.monotonic(),
        make_system=functools.partial(fam.Control, precision=precision), **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precisions", nargs="+")
    args = ap.parse_args(argv)

    from bench import gen, run
    bench = run.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    mix = gen.load_mix(cell["traffic"])
    limits = run.load_limits(cell["name"])
    if not os.environ.get(run.CACHE_ENV):
        os.environ[run.CACHE_ENV] = os.path.join(ROOT, ".jax_cache")
    for seed in args.seeds:
        for precision in args.precisions or [cfg["control"]]:
            out = control_run(cfg, mix, limits, seed=seed,
                              seconds=args.seconds, precision=precision,
                              chips=cell["chips"])
            print(json.dumps({
                "workload": args.workload, "seed": seed,
                "precision": precision, "correct": out["correct"],
                "attempted": out["attempted"], "device": out["device"],
                "compared": out["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
