"""The reduction from a profiler trace to the per-layer numbers."""
from __future__ import annotations

import json
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_interval_arithmetic():
    m = tr.merge([(5, 7), (0, 2), (1, 3), (6, 9), (10, 10)])
    assert m == [(0, 3), (5, 9), (10, 10)]
    assert tr.length(m) == 7
    assert tr.intersect(m, [(2, 6), (8, 12)]) == [(2, 3), (5, 6), (8, 9)]
    assert tr.intersect([], m) == []


@pytest.fixture(scope="module")
def small():
    """A trace recorded on a TPU v5 lite by ``run_cell`` for paper-serve at
    a small size (vocab 500, bags of 16, batch 8, 200 requests in 1 s), and
    the result line that run printed."""
    import gzip

    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, "paper-serve-small.xplane.pb.gz")) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    with open(os.path.join(DATA, "paper-serve-small.json")) as f:
        return tr.Trace(pd), json.load(f)


def test_the_recorded_trace_reduces_as_on_the_chip(small):
    t, res = small
    w = t.window()
    assert (w[1] - w[0]) * 1e-9 == pytest.approx(res["device"]["window_s"])
    assert t.busy_s(w) == pytest.approx(res["device"]["busy_s"])
    assert t.chips == ["/device:TPU:0"]
    steps_s, steps = t.module_time("jit_serve", w)
    assert steps == 25                       # 200 requests, batches of 8
    assert steps_s / steps * 1e3 == pytest.approx(
        res["metrics"]["step_ms.serve"]["value"])
    k_s, kernels = t.custom_call_time("jit_serve", w)
    assert kernels == 25                     # one bag kernel a step
    assert 0 < k_s < steps_s
    assert k_s / steps * 1e3 == pytest.approx(
        res["metrics"]["bag_kernel_ms.serve"]["value"])
    idle, total = t.idle_within(t.spans("assemble", "step"))
    assert 0 < idle < total
    assert idle / total * 100 == pytest.approx(
        res["metrics"]["device_idle.serve"]["value"])


def test_host_spans_and_device_events_share_a_clock_to_2_ms(small):
    """Every run of the step starts within 2 ms of the benchmark's ``step``
    span that dispatched it. (On this trace the device's clock reads about
    1.05 ms early: a run starts that long before its host span, so a
    reading from the trace needs host spans much longer than that.)"""
    t, _ = small
    steps = t.spans("step")
    runs = [m for m in t.modules["/device:TPU:0"]
            if m.name.startswith("jit_serve(")]
    assert len(runs) == len(steps) == 25
    skew = [m.start - s for (s, _), m in zip(steps, runs)]
    assert all(abs(d) < 2e6 for d in skew)


def test_breakdown_is_short_and_labelled(small):
    t, _ = small
    b = t.breakdown(t.window(), ("wait", "submit", "assemble", "step"))
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert any("tpu_custom_call" in name for name, _ in b["device_ops"])
    assert {name for name, _ in b["idle_gaps"]} <= {
        "wait", "submit", "assemble", "step", "other"}
    secs = [s for _, s in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
