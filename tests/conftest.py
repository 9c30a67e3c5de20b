# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# single real CPU device. Multi-device distribution tests run in subprocesses
# that set --xla_force_host_platform_device_count themselves (test_dist.py).
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import pytest


@pytest.fixture
def host_trace(tmp_path):
    """``record(fn)`` runs ``fn`` under a jax profiler trace and returns
    ``(fn's result, spans)``: the events of the trace's ``/host:CPU`` plane
    as ``{name: [(start_ns, end_ns, stats), ...]}`` in start order."""
    def record(fn):
        import glob
        import warnings

        import jax
        from jax.profiler import ProfileData
        with jax.profiler.trace(str(tmp_path)):
            out = fn()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        spans = {}
        with warnings.catch_warnings():
            # jaxlib's event_stats type warns on first use that it has no
            # __module__; nothing here can act on that
            warnings.simplefilter("ignore", DeprecationWarning)
            for plane in ProfileData.from_file(path).planes:
                if plane.name != "/host:CPU":
                    continue
                for line in plane.lines:
                    for e in line.events:
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns, dict(e.stats)))
        for evs in spans.values():
            evs.sort(key=lambda ev: ev[0])
        return out, spans
    return record
