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


@pytest.fixture
def ring_depths():
    """``depths(fn, *args)``: the row-copy ring depth of every
    ``updlrm_bag`` kernel that ``fn(*args)`` traces, read off the kernel's
    VMEM ring scratch (its first scratch operand). Only traces ``fn``, so
    it reads a compiled-mode kernel on a machine with no TPU too."""
    def depths(fn, *args):
        import jax
        from jax.extend import core as jcore

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if (eqn.primitive.name == "pallas_call"
                        and eqn.params["name"] == "updlrm_bag"):
                    kernel = eqn.params["jaxpr"]
                    n = eqn.params["grid_mapping"].num_scratch_operands
                    yield kernel.invars[len(kernel.invars) - n].aval.shape[0]
                for p in eqn.params.values():
                    for sub in p if isinstance(p, (tuple, list)) else [p]:
                        if isinstance(sub, jcore.ClosedJaxpr):
                            yield from walk(sub.jaxpr)
                        elif isinstance(sub, jcore.Jaxpr):
                            yield from walk(sub)

        return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))
    return depths
