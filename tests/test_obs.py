"""Tier-1 tests for the observability layer (src/repro/obs/).

What is pinned here and why it matters:

* Histogram bucket/merge/percentile properties — the registry's p50/p99
  come from FIXED log-spaced buckets so merges are exact; a drifting bucket
  layout or a quantile outside the observed [min, max] silently corrupts
  every latency number the benches and CLIs report.
* ``empirical_percentile`` bit-compatibility — it is the ONE home of the
  sorted-index convention (``s[min(len-1, int(q*len))]``) the committed
  BENCH baselines were generated with; a convention change would show up as
  a fake bench regression.
* Span nesting + Chrome-trace schema — the exported JSON must stay loadable
  by Perfetto ('M' metadata first, 'X' complete events with ts/dur, 'i'
  instants with a scope).
* Registry snapshot determinism — CI gates on the snapshot's key-path
  schema (benchmarks/check_regression.py --metrics-baseline), so two runs
  of one configuration must produce structurally identical documents.
* Zero-recompile — tracing a jit'd step must not add executables; the
  whole obs layer is host-clock-only by contract.
* Profiler spans — every span is also a ``jax.profiler.TraceAnnotation``,
  so a profiler session sees it by name whether or not the tracer records;
  the package root still imports, and spans still work, without jax.
"""
import json
import math
import os

import numpy as np
import pytest

from repro.obs import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    NULL_TRACER,
    PeriodicMetricsWriter,
    Tracer,
    chrome_trace_events,
    empirical_p50,
    empirical_p99,
    empirical_percentile,
    log_bucket_bounds,
    prometheus_text,
    snapshot_doc,
    summary_dict,
    summary_line,
    write_chrome_trace,
    write_metrics_json,
)


# ---------------------------------------------------------------------------
# histogram properties
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_bucket_bounds_fixed_and_sorted(self):
        b = log_bucket_bounds()
        assert b == DEFAULT_BUCKETS
        assert list(b) == sorted(b)
        # 8/decade => adjacent bounds a constant 10**(1/8) apart
        ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
        assert np.allclose(ratios, 10 ** 0.125)

    def test_counts_partition_observations(self):
        h = Histogram("t")
        rng = np.random.default_rng(0)
        xs = rng.lognormal(mean=2.0, sigma=3.0, size=500)
        for x in xs:
            h.observe(x)
        assert h.count == 500
        assert sum(h.counts) == 500
        assert h.sum == pytest.approx(float(np.sum(xs)))
        assert h.min == pytest.approx(float(np.min(xs)))
        assert h.max == pytest.approx(float(np.max(xs)))

    def test_quantile_within_observed_range_and_one_bucket_of_exact(self):
        h = Histogram("t")
        rng = np.random.default_rng(1)
        xs = rng.lognormal(mean=0.0, sigma=2.0, size=1000)
        for x in xs:
            h.observe(x)
        for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
            est = h.quantile(q)
            assert h.min <= est <= h.max
            exact = empirical_percentile(xs, q)
            # bucket answer is the covering bucket's UPPER bound: never
            # below the exact quantile (conservative), and at most one
            # bucket ratio (10**(1/8) ~ 1.33x) above it
            assert est >= exact * 0.999
            assert est <= max(exact * 10 ** 0.125 * 1.001, h.min)

    def test_empty_and_single(self):
        h = Histogram("t")
        assert h.quantile(0.99) == 0.0
        assert h.mean == 0.0
        h.observe(7.0)
        assert h.quantile(0.5) == pytest.approx(7.0)
        assert h.quantile(0.99) == pytest.approx(7.0)

    def test_merge_is_exact(self):
        """merge(a, b) must equal the histogram that saw both streams —
        the property that lets shards/processes combine without samples."""
        rng = np.random.default_rng(2)
        xs = rng.lognormal(sigma=2.5, size=300)
        ys = rng.lognormal(sigma=1.5, size=200) * 50.0
        ha, hb, hall = Histogram("a"), Histogram("b"), Histogram("all")
        for x in xs:
            ha.observe(x)
            hall.observe(x)
        for y in ys:
            hb.observe(y)
            hall.observe(y)
        ha.merge(hb)
        assert ha.counts == hall.counts
        assert ha.count == hall.count
        assert ha.sum == pytest.approx(hall.sum)
        assert ha.min == hall.min and ha.max == hall.max
        for q in (0.5, 0.9, 0.99):
            assert ha.quantile(q) == hall.quantile(q)

    def test_merge_rejects_different_bounds(self):
        ha = Histogram("a")
        hb = Histogram("b", bounds=log_bucket_bounds(per_decade=4))
        with pytest.raises(ValueError):
            ha.merge(hb)

    def test_unsorted_bounds_rejected(self):
        with pytest.raises(ValueError):
            Histogram("t", bounds=(2.0, 1.0))

    def test_property_sweep_hypothesis(self):
        hypothesis = pytest.importorskip("hypothesis")
        st = pytest.importorskip("hypothesis.strategies")

        @hypothesis.given(st.lists(
            st.floats(min_value=1e-6, max_value=1e8,
                      allow_nan=False, allow_infinity=False),
            min_size=1, max_size=200))
        @hypothesis.settings(deadline=None, max_examples=50)
        def check(xs):
            h = Histogram("t")
            for x in xs:
                h.observe(x)
            assert sum(h.counts) == len(xs)
            for q in (0.0, 0.5, 0.99, 1.0):
                assert h.min <= h.quantile(q) <= h.max

        check()


# ---------------------------------------------------------------------------
# the empirical percentile convention
# ---------------------------------------------------------------------------

class TestEmpiricalPercentile:
    def test_matches_legacy_convention(self):
        """Bit-for-bit the historical MicroBatcher/bench convention — the
        committed BENCH baselines depend on this exact index rule."""
        rng = np.random.default_rng(3)
        for n in (1, 2, 3, 7, 100, 199):
            xs = list(rng.normal(size=n))
            for q in (0.5, 0.9, 0.99):
                s = sorted(xs)
                legacy = s[min(len(s) - 1, int(q * len(s)))]
                assert empirical_percentile(xs, q) == legacy

    def test_empty_and_aliases(self):
        assert empirical_percentile([], 0.99) == 0.0
        xs = [5.0, 1.0, 3.0]
        assert empirical_p50(xs) == empirical_percentile(xs, 0.50)
        assert empirical_p99(xs) == empirical_percentile(xs, 0.99)

    def test_bench_p99_delegates_here(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bench_workload", os.path.join(os.path.dirname(__file__), "..",
                                           "benchmarks", "bench_workload.py"))
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        xs = list(np.random.default_rng(4).normal(size=137))
        assert bench.p99(xs) == empirical_p99(xs)


# ---------------------------------------------------------------------------
# tracer + chrome trace export
# ---------------------------------------------------------------------------

class TestTracer:
    def test_span_nesting_depth_and_args(self):
        tr = Tracer()
        with tr.span("outer", batch=3):
            with tr.span("inner"):
                pass
        assert tr.span_names() == {"outer", "inner"}
        (outer,) = tr.spans("outer")
        (inner,) = tr.spans("inner")
        assert outer.depth == 0 and inner.depth == 1
        assert outer.args == {"batch": 3}
        # inner completes first but starts later, inside the outer window
        assert outer.ts_us <= inner.ts_us
        assert inner.ts_us + inner.dur_us <= outer.ts_us + outer.dur_us + 1.0

    def test_span_records_on_exception(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        assert len(tr.spans("boom")) == 1

    def test_disabled_tracer_records_nothing(self):
        tr = Tracer(enabled=False)
        with tr.span("a"):
            tr.instant("b")
        assert tr.records == [] and tr.instants == []
        assert NULL_TRACER.enabled is False

    def test_span_and_child_reach_the_profiler(self, host_trace):
        """Spans go out as ``TraceAnnotation``s: a profiler session sees
        them by name on the host's plane, the child inside its parent,
        with the span's args as the event's stats."""
        tr = Tracer()

        def work():
            with tr.span("obs.parent", batch=7):
                with tr.span("obs.child"):
                    pass
        _, spans = host_trace(work)
        ((p0, p1, stats),) = spans["obs.parent"]
        ((c0, c1, _),) = spans["obs.child"]
        assert p0 <= c0 <= c1 <= p1
        assert stats == {"batch": 7}
        assert tr.span_names() == {"obs.parent", "obs.child"}

    def test_disabled_tracer_annotates_but_records_nothing(self,
                                                           host_trace):
        tr = Tracer(enabled=False)

        def work():
            with tr.span("obs.quiet", step=1):
                pass
            with NULL_TRACER.span("obs.null"):
                pass
        _, spans = host_trace(work)
        assert len(spans["obs.quiet"]) == 1 and len(spans["obs.null"]) == 1
        assert tr.records == [] and NULL_TRACER.records == []

    def test_obs_imports_and_spans_without_jax(self):
        """The package root stays jax-free (``repro.dist.fault`` produces
        spans): with jax blocked it imports, and a span is a plain
        context."""
        import subprocess
        import sys
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        code = ("import sys; sys.modules['jax'] = None\n"
                "from repro.obs import NULL_TRACER, Tracer\n"
                "tr = Tracer()\n"
                "with tr.span('a', k=1), NULL_TRACER.span('b'):\n"
                "    pass\n"
                "assert tr.span_names() == {'a'}\n"
                "assert 'jax.profiler' not in sys.modules\n"
                "print('ok')\n")
        env = {**os.environ, "PYTHONPATH": os.path.abspath(src)}
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "ok"

    def test_total_us_sums_same_name(self):
        tr = Tracer()
        for _ in range(3):
            with tr.span("step"):
                pass
        assert tr.total_us("step") == pytest.approx(
            sum(r.dur_us for r in tr.spans("step")))

    def test_chrome_trace_schema(self, tmp_path):
        tr = Tracer()
        with tr.span("rewrite", batch=0):
            pass
        with tr.span("device_step"):
            pass
        tr.instant("swap_live", reason="drift")
        path = str(tmp_path / "trace.json")
        n = write_chrome_trace(tr, path)
        with open(path) as fh:
            doc = json.load(fh)
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        events = doc["traceEvents"]
        assert len(events) == n
        # metadata first (Perfetto uses it to name tracks)
        assert events[0]["ph"] == "M" and events[0]["name"] == "process_name"
        xs = [e for e in events if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"rewrite", "device_step"}
        for e in xs:
            assert {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                    "args"} <= set(e)
            assert e["dur"] >= 0.0
        (inst,) = [e for e in events if e["ph"] == "i"]
        assert inst["name"] == "swap_live" and inst["s"] == "t"
        # spans in start-time order
        assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)

    def test_chrome_trace_events_deterministic_pid(self):
        tr = Tracer()
        with tr.span("a"):
            pass
        ev = chrome_trace_events(tr, pid=7)
        assert all(e["pid"] == 7 for e in ev)


# ---------------------------------------------------------------------------
# registry + export
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_get_or_create_and_kind_conflict(self):
        reg = MetricRegistry()
        c1 = reg.counter("a.total")
        assert reg.counter("a.total") is c1
        with pytest.raises(TypeError):
            reg.gauge("a.total")
        with pytest.raises(TypeError):
            reg.histogram("a.total")

    def test_counter_rejects_negative(self):
        c = Counter("c")
        c.inc(2)
        with pytest.raises(ValueError):
            c.inc(-1)
        g = Gauge("g")
        g.inc(-1)                      # gauges may go down
        assert g.value == -1.0

    def test_snapshot_schema_deterministic(self):
        """Two registries with the same metric set but DIFFERENT observed
        values must export identical key-path structure — the invariant the
        CI metrics-schema gate (check_regression.py) relies on."""
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "benchmarks"))
        try:
            from check_regression import key_paths
        finally:
            sys.path.pop(0)

        def build(seed):
            reg = MetricRegistry()
            reg.counter("serve.requests_total")
            reg.gauge("runtime.cache_version")
            h = reg.histogram("serve.request_latency_ms")
            for x in np.random.default_rng(seed).lognormal(size=20):
                h.observe(x)
            return snapshot_doc(reg, label=f"run-{seed}")

        a, b = build(0), build(1)
        assert a != b                             # values differ...
        assert key_paths(a) == key_paths(b)       # ...schema does not
        hsnap = a["metrics"]["serve.request_latency_ms"]
        assert set(hsnap) == {"type", "count", "sum", "min", "max", "mean",
                              "p50", "p99", "buckets"}
        # never-fired metrics still export (pre-registration contract)
        assert a["metrics"]["serve.requests_total"]["value"] == 0.0

    def test_snapshot_sorted_and_json_stable(self):
        reg = MetricRegistry()
        reg.counter("z.last")
        reg.counter("a.first")
        assert list(reg.snapshot()) == ["a.first", "z.last"]
        assert reg.to_json() == reg.to_json()

    def test_prometheus_text(self):
        reg = MetricRegistry()
        reg.counter("serve.requests_total", "total requests").inc(5)
        h = reg.histogram("serve.request_latency_ms")
        h.observe(0.5)
        h.observe(2.0)
        text = prometheus_text(reg)
        assert "# TYPE serve_requests_total counter" in text
        assert "serve_requests_total 5.0" in text
        assert "# HELP serve_requests_total total requests" in text
        assert '_bucket{le="+Inf"} 2' in text
        assert "serve_request_latency_ms_count 2" in text
        # cumulative buckets are monotone
        cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
                if "_bucket{" in line]
        assert cums == sorted(cums)

    def test_summary_line_parses(self):
        reg = MetricRegistry()
        reg.counter("a.total").inc(3)
        reg.histogram("b.ms").observe(1.0)
        line = summary_line(reg)
        assert line.startswith("OBS_SUMMARY ")
        parsed = json.loads(line.split(" ", 1)[1])
        assert parsed == summary_dict(reg)
        assert parsed["a.total"] == 3.0
        assert set(parsed["b.ms"]) == {"count", "mean", "p50", "p99"}

    def test_periodic_writer_cadence(self, tmp_path):
        reg = MetricRegistry()
        reg.counter("a.total")
        path = str(tmp_path / "m.json")
        w = PeriodicMetricsWriter(reg, path, every=4, label="t")
        wrote = [w.maybe_write(b) for b in range(10)]
        assert wrote == [False, False, False, False, True,
                         False, False, False, True, False]
        assert w.n_writes == 2
        w.flush()
        assert w.n_writes == 3
        with open(path) as fh:
            doc = json.load(fh)
        assert doc["meta"] == {"label": "t", "schema": 2}
        assert not os.path.exists(path + ".tmp")

    def test_periodic_writer_disabled_cadence(self, tmp_path):
        reg = MetricRegistry()
        w = PeriodicMetricsWriter(reg, str(tmp_path / "m.json"), every=0)
        assert not any(w.maybe_write(b) for b in range(20))
        assert w.n_writes == 0

    def test_write_metrics_json_roundtrip(self, tmp_path):
        reg = MetricRegistry()
        reg.gauge("x.v").set(2.5)
        path = str(tmp_path / "out.json")
        doc = write_metrics_json(reg, path, label="lab")
        with open(path) as fh:
            assert json.load(fh) == doc


# ---------------------------------------------------------------------------
# integration: producers + the zero-recompile contract
# ---------------------------------------------------------------------------

class TestIntegration:
    def test_microbatcher_routes_metrics(self):
        from repro.serve.serve_step import MicroBatcher, Request
        reg = MetricRegistry()
        mb = MicroBatcher(4, pad_request={"f": np.zeros(2, np.int32)},
                          metrics=reg)
        reqs = [Request(rid=i, features={"f": np.zeros(2, np.int32)})
                for i in range(3)]
        mb.complete(reqs)
        assert reg.get("serve.requests_total").value == 3.0
        assert reg.get("serve.request_latency_ms").count == 3
        assert mb.p99() == empirical_p99(mb.latencies)

    def test_tracing_jit_step_zero_recompile(self):
        """A span around a jit'd call must not add executables: the tracer
        reads only the host clock, so every traced call after warm-up is a
        cache hit (zero new compile events, one executable) — same contract
        the serve CLIs assert end-to-end. jax.monitoring may fire several
        compile events for ONE compilation, so we assert the post-warm-up
        delta is zero rather than pinning the warm-up count."""
        import jax
        import jax.numpy as jnp
        from repro.launch.serve import CompileProbe
        reg = MetricRegistry()
        probe = CompileProbe(metrics=reg)
        tr = Tracer()

        @jax.jit
        def step(x):
            return x * 2.0

        # inputs built OUTSIDE the probed window: jnp.ones/mul compile too
        xs = [jax.block_until_ready(jnp.ones(8) * i) for i in range(3)]
        jax.block_until_ready(step(xs[0]))  # warm-up compiles
        warm = probe.compiles
        assert warm >= 1
        for i in range(3):
            with tr.span("device_step", batch=i):
                jax.block_until_ready(step(xs[i]))
        assert probe.compiles - warm == 0
        assert reg.get("jax.compiles_total").value >= 1.0
        assert step._cache_size() == 1
        assert len(tr.spans("device_step")) == 3
