"""The readers of the program's own spans (``serve.batch``, ``serve.h2d``,
``serve.stack``): exact values on a trace with known spans, silence where
there is no chip or no such span, and the spans' place inside the
benchmark's ``assemble`` span in a real run on the CPU."""
from __future__ import annotations

import shutil
import tempfile
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import run
from bench.trace_reduce import Trace
from conftest import small_config, small_mix

NEW = ["h2d_ms", "h2d_per_batch", "h2d_p99_ms", "stack_ms",
       "device_idle_h2d.serve"]
MS = 1_000_000                                # ns

#: two batches inside a 10 ms window and one after it, which no reader
#: counts; times in ms
HOST = {
    "window": [(0, 10)],
    "assemble": [(1, 4), (5, 8)],
    "step": [(4, 4.8), (8, 8.8)],
    "serve.batch": [(1, 4), (5, 8), (11, 12)],
    "serve.h2d": [(1, 1.5), (1.5, 2.5), (5, 5.5), (5.5, 6), (11, 11.4)],
    "serve.stack": [(2.5, 3.5), (6, 6.5), (11.4, 11.6)],
}
#: the chip runs inside the first batch's first transfer, in each step and
#: inside the second batch's first transfer
OPS = [(1.2, 1.4), (4, 4.8), (5.2, 5.3), (8, 8.8)]


def _events(spans):
    return [NS(name=name, start_ns=s * MS, end_ns=e * MS)
            for name, ivs in spans.items() for s, e in ivs]


def _run(host, ops):
    planes = [NS(name="/host:CPU", lines=[NS(name="python",
                                             events=_events(host))])]
    if ops is not None:
        planes.append(NS(name="/device:TPU:0", lines=[NS(
            name="XLA Ops", events=_events({"%fusion.1 = f32[8] fusion()":
                                            ops}))]))
    tr = Trace(NS(planes=planes))
    return NS(trace=tr, trace_window=tr.window())


@pytest.mark.parametrize("name,want", [
    ("h2d_ms", (0.5 + 1.0 + 0.5 + 0.5) / 2),
    ("h2d_per_batch", 4 / 2),
    ("h2d_p99_ms", 0.5 + 0.97 * 0.5),        # [0.5, 0.5, 0.5, 1.0], linear
    ("stack_ms", (1.0 + 0.5) / 2),
    # idle in the transfers: 2.5 ms less 0.3 busy; in assemble or step:
    # 7.6 ms less 1.9 busy
    ("device_idle_h2d.serve", (2.5 - 0.3) / (7.6 - 1.9) * 100),
])
def test_reader_gives_exact_values_on_known_spans(name, want):
    assert run.reader(name).read(_run(HOST, OPS)) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_without_a_chip(name):
    assert run.reader(name).read(_run(HOST, None)) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_where_the_program_has_no_spans(name):
    """The parent of the program that added these spans has none."""
    host = {k: v for k, v in HOST.items() if not k.startswith("serve.")}
    assert run.reader(name).read(_run(host, OPS)) is None


@pytest.mark.parametrize("name", NEW)
def test_new_entry_is_found_by_name(name):
    (m,) = [m for m in run.load_benchmark()["per_layer"]
            if m["name"] == name]
    assert m["workloads"] == ["paper-serve"]
    assert m["source"] == "device_trace"
    assert hasattr(run.reader(name), "read")


def test_every_transfer_lies_inside_an_assemble_span_on_the_cpu():
    """A small open-loop window of paper-serve under a profiler trace, as
    ``run_cell`` takes it: each batch is one ``serve.batch`` span with
    2 x batch ``serve.h2d`` spans (dense and sparse per request) and two
    ``serve.stack`` spans, all inside the benchmark's ``assemble``."""
    import jax

    from bench import drive, gen
    from bench.families import dlrm as fam
    from bench.trace_reduce import find_xplane, intersect, length, merge

    seed = 2**31 + 29
    cfg, mix = small_config("updlrm-paper"), small_mix("goodreads-open")
    traffic = gen.make_traffic(cfg, mix, seed, 0.3)
    system = fam.System(cfg, fam.make_weights(cfg, seed), int(mix["batch"]))
    drive.warm_serve(system, traffic)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        record = drive.serve_open(system, traffic)
        jax.profiler.stop_trace()
        tr = Trace.from_file(find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
        system.free()
    n_batches = len(record.batches)
    assert n_batches > 1
    assemble = tr.spans("assemble")
    assert len(assemble) == n_batches
    for name, per_batch in (("serve.batch", 1),
                            ("serve.h2d", 2 * system.batch),
                            ("serve.stack", 2)):
        spans = tr.host[name]
        assert len(spans) == per_batch * n_batches, name
        for s, e in spans:
            assert length(intersect(merge([(s, e)]), assemble)) == e - s
    h2d = np.array([e - s for s, e in tr.host["serve.h2d"]])
    assert (h2d > 0).all()
