#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json`` at the
checkout's root: its configuration file (``configs``), its traffic mix
(``bench/traffic/<traffic>.json``), the family module that builds the
program's serve path and the reference (``bench/families/<family>.py``),
each metric's reader (``bench/metrics/<name>.py``, or the part of the name
before its first dot) and the cell's limits (``bench/limits/<cell>.json``).

A run: make the traffic and the weights from the seed, build and compile
the program's serve step (JAX's persistent compile cache lives in
``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` names one),
warm up, then measure for ``--seconds``; with ``--trace 1`` the window is
traced and the per-layer metrics are read from the trace. After the window
the device's peak memory is read, the program's state is freed and the
served scores are compared with the plain reference's. The last line of
standard output is one JSON object; the numbers compared, each beside its
limit, are the last lines of standard error and the last key of that
object.

Without an accelerator, or with fewer chips than the cell asks for, the run
prints no result and exits with code 2.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, metrics_dir: str = os.path.join(HERE, "metrics")):
    """The reader of metric ``name``: ``metrics/<name>.py``, else the file of
    the part of the name before its first dot."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(metrics_dir, f"{stem}.py")
        if os.path.exists(path):
            return load_module(path, f"bench_metric_{stem.replace('.', '_')}")
    raise FileNotFoundError(f"no reader for metric {name!r} in {metrics_dir}")


def family(name: str):
    import importlib
    return importlib.import_module(f"bench.families.{name}")


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metrics a run of ``cell`` reports: its end-to-end ones, or with a
    trace its per-layer ones."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_limits(cell: str) -> dict:
    path = os.path.join(HERE, "limits", f"{cell}.json")
    with open(path) as f:
        return {k: v["limit"] for k, v in json.load(f).items()}


class Run:
    """What one run measured; the metric readers read it."""

    def __init__(self, cfg, mix, fam, system, traffic, record, setup_s,
                 trace=None, peak=None):
        self.cfg, self.mix, self.fam = cfg, mix, fam
        self.mode = mix["mode"]
        self.module = system.module
        self.traffic, self.record = traffic, record
        self.setup_s = setup_s
        self.trace = trace
        self.trace_window = trace.window() if trace is not None else None
        self.peak = peak

    def latencies_s(self):
        if self.mode != "open":
            return None
        return self.record.done - self.record.due

    def step_device_s(self):
        if self.trace is None:
            return 0.0, 0
        return self.trace.module_time(self.module, self.trace_window)

    def kernel_device_s(self):
        if self.trace is None:
            return 0.0, 0
        return self.trace.custom_call_time(self.module, self.trace_window)

    def _batches(self):
        """Host arrays of every batch the window ran."""
        if self.mode == "closed":
            ring = self.traffic.ring
            return [ring[i] for i, _ in self.record.outputs]
        feats, B = self.traffic.feats, self.traffic.batch
        out = []
        for ids in self.record.batch_ids:
            rows = list(ids) + [0] * (B - len(ids))
            out.append({k: v[rows] for k, v in feats.items()})
        return out

    def _mean_work(self, fn):
        batches = self._batches()
        seen = {}
        total = None
        for b in batches:
            w = seen.get(id(b))
            if w is None:
                w = seen[id(b)] = fn(self.cfg, b)
            total = w if total is None else total + w
        return total * (1.0 / len(batches))

    def step_work(self):
        return self._mean_work(self.fam.step_work)

    def lookup_work(self):
        return self._mean_work(self.fam.lookup_work)

    def min_time(self, w):
        from bench.work import min_time
        return min_time(w, self.peak)


def compare(fam, cfg, seed, traffic, record, limits, mode) -> dict:
    """The served scores against the reference's: the widest gap, and how
    many answers never came."""
    import numpy as np
    block = int(cfg["reference_block"])
    if mode == "open":
        ref = fam.reference_scores(cfg, seed, traffic.feats, block=block)
        got = record.scores
        scored = ~np.isnan(got)
        unscored = int((~scored).sum())
        gaps = np.abs(got[scored] - ref[scored])
        over = int((gaps > limits["score_gap"]).sum())
        n = len(got)
    else:
        refs = {i: fam.reference_scores(cfg, seed, traffic.ring[i],
                                        block=block)
                for i in sorted({i for i, _ in record.outputs})}
        gaps = np.concatenate([np.abs(out - refs[i])
                               for i, out in record.outputs])
        unscored = int(np.isnan(gaps).sum())
        gaps = gaps[~np.isnan(gaps)]
        over = int((gaps > limits["score_gap"]).sum())
        n = len(gaps)
    gap = float(gaps.max()) if len(gaps) else float("inf")
    return {"n": n, "failed": unscored + over,
            "compared": {"score_gap": {"value": gap,
                                       "limit": limits["score_gap"]},
                         "unscored": {"value": unscored, "limit": 0}}}


def device_info(jax) -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def run_cell(cfg: dict, mix: dict, limits: dict, *, seed: int,
             seconds: float, trace: bool, t_start: float,
             require_chip: bool = True, chips: int = 1,
             metrics: list | None = None, make_system=None) -> dict:
    """One run of one cell; returns the result object. ``make_system``
    stands in for the family's ``System`` (the control puts the reference
    there)."""
    import jax

    from bench import drive, gen, work

    info = device_info(jax)
    if require_chip and (info["platform"] == "cpu" or info["count"] < chips):
        raise SystemExit(2)
    peak = work.peaks(info["kind"]) if require_chip else \
        {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    fam = family(cfg["family"])

    marks = [("start", t_start), ("jax", drive.clock())]
    traffic = gen.make_traffic(cfg, mix, seed, seconds)
    marks.append(("traffic", drive.clock()))
    weights = jax.block_until_ready(fam.make_weights(cfg, seed))
    marks.append(("weights", drive.clock()))
    system = (make_system or fam.System)(cfg, weights, int(mix["batch"]))
    del weights
    marks.append(("compile", drive.clock()))
    if mix["mode"] == "open":
        drive.warm_serve(system, traffic)
    else:
        drive.warm_bulk(system, traffic.ring)
    marks.append(("warm-up", drive.clock()))
    print("set-up seconds: " + ", ".join(
        f"{b[0]} {b[1] - a[1]:.3f}" for a, b in zip(marks, marks[1:]))
        + " (of which " + ", ".join(f"{k} {v:.3f}"
                                    for k, v in system.setup_marks) + ")",
        file=sys.stderr)

    compiles = []
    listener = (lambda name, secs, **kw: compiles.append(name)
                if "compile" in name else None)
    jax.monitoring.register_event_duration_secs_listener(listener)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            # the benchmark's own spans (TraceAnnotation, host level 1) but
            # neither the runtime's nor the Python tracer's: they would time
            # every call of the host loop they are measuring
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        setup_s = drive.clock() - t_start
        if mix["mode"] == "open":
            record = drive.serve_open(system, traffic)
        else:
            record = drive.bulk_closed(system, traffic.ring, seconds)
        if trace:
            jax.profiler.stop_trace()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)

    stats = jax.devices()[0].memory_stats() or {}
    info["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    system.free()

    tr = None
    if trace:
        from bench.trace_reduce import Trace, find_xplane
        tr = Trace.from_file(find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(cfg, mix, fam, system, traffic, record, setup_s, tr, peak)
    result_metrics = {}
    for m in metrics or []:
        v = reader(m["name"]).read(run)
        if v is not None:
            result_metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    cmp = compare(fam, cfg, seed, traffic, record, limits, mix["mode"])
    correct = cmp["failed"] == 0 and all(
        c["value"] <= c["limit"] for c in cmp["compared"].values())
    if trace:
        info["busy_s"] = tr.busy_s(run.trace_window)
        info["window_s"] = (run.trace_window[1] - run.trace_window[0]) * 1e-9
    out = {"correct": bool(correct), "attempted": cmp["n"],
           "failed": cmp["failed"], "metrics": result_metrics,
           "device": info}
    if trace:
        labels = ("wait", "submit", "assemble", "step", "dispatch", "drain")
        out["breakdown"] = tr.breakdown(run.trace_window, labels)
    if mix["mode"] == "open":
        # how long after the last request was due the window closed: a
        # backlog that grows through the run shows here first
        out["drain_s"] = float(record.window[1] - record.due[-1])
    out["compiles_in_window"] = len(compiles)
    out["compared"] = cmp["compared"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(ROOT, configs[cell["config"]]["file"])) as f:
        cfg = json.load(f)
    from bench import gen
    mix = gen.load_mix(cell["traffic"])

    if not os.environ.get(CACHE_ENV):
        os.environ[CACHE_ENV] = os.path.join(ROOT, ".jax_cache")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    info = device_info(jax)
    if info["platform"] == "cpu" or info["count"] < cell["chips"]:
        print(f"needs {cell['chips']} accelerator chip(s); JAX found "
              f"{info['count']} {info['platform']} device(s)",
              file=sys.stderr)
        return 2

    result = run_cell(cfg, mix, load_limits(cell["name"]), seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      t_start=T_START, chips=cell["chips"],
                      metrics=cell_metrics(bench, cell["name"],
                                           bool(args.trace)))
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
