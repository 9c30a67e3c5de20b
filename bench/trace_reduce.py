"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

``jax.profiler.ProfileData`` reads the file. A device plane is one whose
name starts with ``/device:TPU:``; its ``XLA Ops`` line holds one event per
operation run on the chip, its ``XLA Modules`` line one per executable run.
The benchmark's own host spans (``jax.profiler.TraceAnnotation``) sit on a
thread line of ``/host:CPU``. The two clocks agree to about a millisecond:
on a v5e the device's runs start about 1.05 ms before the host span that
dispatched them (``bench/tests/test_trace_reduce.py``), so a number that
sets device events against host spans wants spans much longer than that.

* ``busy``: the union of the op intervals of a chip;
* ``module_time``: the summed device time of one executable's runs, and
  their count;
* ``custom_call_time``: the summed device time of the Pallas kernels
  (``tpu_custom_call``) inside those runs;
* ``idle_within``: the part of a set of host spans in which the chip ran
  nothing;
* ``breakdown``: the ops that took the most time, and the longest idle gaps
  labelled by the host span the benchmark was in.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_MARK = "tpu_custom_call"
#: idle gaps shorter than this are the seams between back-to-back ops
MIN_GAP_NS = 1000


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def merge(intervals) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def intersect(a, b) -> list:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


class Event:
    __slots__ = ("name", "start", "end")

    def __init__(self, name, start, end):
        self.name, self.start, self.end = name, start, end

    @property
    def dur(self) -> float:
        return self.end - self.start


class Trace:
    """The events of one trace, times in ns on the trace's clock."""

    def __init__(self, pd):
        self.ops = defaultdict(list)       # device plane -> [Event]
        self.modules = defaultdict(list)   # device plane -> [Event]
        self.host = defaultdict(list)      # span name -> [(start, end)]
        self._busy = {}                    # device plane -> merged op spans
        for plane in pd.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                for line in plane.lines:
                    dest = {OPS_LINE: self.ops,
                            MODULES_LINE: self.modules}.get(line.name)
                    if dest is None:
                        continue
                    dest[plane.name].extend(
                        Event(e.name, e.start_ns, e.end_ns)
                        for e in line.events)
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for e in line.events:
                        self.host[e.name].append((e.start_ns, e.end_ns))

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls(ProfileData.from_file(path))

    @property
    def chips(self) -> list:
        return sorted(p for p in self.ops if self.ops[p])

    def spans(self, *names) -> list:
        """Merged host spans of the given names."""
        return merge(iv for n in names for iv in self.host.get(n, []))

    def window(self, name: str = "window") -> tuple:
        spans = self.spans(name)
        if not spans:
            raise ValueError(f"no host span {name!r} in the trace")
        return spans[0][0], spans[-1][1]

    def busy(self, chip: str, within=None) -> list:
        if chip not in self._busy:
            self._busy[chip] = merge((e.start, e.end) for e in self.ops[chip])
        merged = self._busy[chip]
        return merged if within is None else intersect(merged, merge(within))

    def busy_s(self, window) -> float:
        """Device-busy seconds inside ``window``, averaged over the chips."""
        chips = self.chips
        if not chips:
            return 0.0
        return sum(length(self.busy(c, [window])) for c in chips) \
            / len(chips) * 1e-9

    def _module_runs(self, chip, module, window):
        lo, hi = window
        return [m for m in self.modules[chip]
                if m.name.split("(")[0] == module and lo <= m.start < hi]

    def module_time(self, module: str, window) -> tuple[float, int]:
        """(device seconds, runs) of executable ``module`` started inside
        ``window``, summed over the chips."""
        t, n = 0.0, 0
        for chip in self.chips:
            runs = self._module_runs(chip, module, window)
            t += sum(m.dur for m in runs)
            n += len(runs)
        return t * 1e-9, n

    def custom_call_time(self, module: str, window) -> tuple[float, int]:
        """(device seconds, kernel events) of the Pallas kernels run inside
        executable ``module``'s runs within ``window``."""
        t, n = 0.0, 0
        for chip in self.chips:
            runs = merge((m.start, m.end) for m in
                         self._module_runs(chip, module, window))
            starts = [s for s, _ in runs]
            for e in self.ops[chip]:
                k = bisect.bisect_right(starts, e.start) - 1
                if k >= 0 and e.start < runs[k][1] and is_kernel(e):
                    t += e.dur
                    n += 1
        return t * 1e-9, n

    def idle_within(self, spans) -> tuple[float, float]:
        """(idle seconds, span seconds) of the merged host ``spans``,
        averaged over the chips."""
        spans = merge(spans)
        total = length(spans)
        chips = self.chips
        if not chips or not total:
            return 0.0, total * 1e-9
        busy = sum(length(self.busy(c, spans)) for c in chips) / len(chips)
        return (total - busy) * 1e-9, total * 1e-9

    def breakdown(self, window, labels, top: int = 10) -> dict:
        """The ``top`` ops by device time and the ``top`` longest idle gaps
        inside ``window``, each gap named by the host span in ``labels``
        that covers most of it."""
        per_op = defaultdict(float)
        gaps = []
        lo, hi = window
        for chip in self.chips:
            for e in self.ops[chip]:
                if lo <= e.start < hi:
                    per_op[e.name] += e.dur * 1e-9
            busy = self.busy(chip, [window])
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            gaps.extend((s, e) for s, e in zip(edges[0::2], edges[1::2])
                        if e - s >= MIN_GAP_NS)
        spans = {n: self.spans(n) for n in labels}
        out_gaps = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
            cover = {n: length(intersect([(s, e)], sp))
                     for n, sp in spans.items()}
            name = max(cover, key=cover.get) if cover and \
                max(cover.values()) > 0 else "other"
            out_gaps.append([name, (e - s) * 1e-9])
        ops = defaultdict(float)
        for name, t in per_op.items():
            ops[op_label(name)] += t
        ops = sorted(([k, v] for k, v in ops.items()),
                     key=lambda kv: -kv[1])[:top]
        return {"device_ops": ops, "idle_gaps": out_gaps}


_HLO = re.compile(r"^%?(\S+) = (\w+)(\[[^\]]*\])?\S* ([\w-]+)\(")


def op_label(name: str) -> str:
    """A short label of an ``XLA Ops`` event, whose name is the whole HLO
    instruction: ``<name> <opcode> <dtype>[<shape>]``, and the target of a
    custom call."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    label = f"{m.group(1)} {m.group(4)} {m.group(2)}{m.group(3) or ''}"
    if KERNEL_MARK in name:
        label += f" {KERNEL_MARK}"
    return label


def is_kernel(e: Event) -> bool:
    """A Pallas kernel: an ``XLA Ops`` event is named by its whole HLO
    instruction, which names its target, tpu_custom_call."""
    return KERNEL_MARK in e.name
