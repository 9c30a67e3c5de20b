"""The measured window: the benchmark's clock and arrival schedule around
the program's own pieces.

``serve_open`` is the plain serve loop of the serving CLI with an open-loop
clock: every request is handed to the program's ``MicroBatcher`` when it is
due (or as soon after as the single host thread gets to it), a batch runs
when one is full, and the tail is padded once the last request is in. A
request's latency runs from its due time to the moment its batch's scores
are ready. ``bulk_closed`` scores a ring of host batches back to back, one
step in flight behind the one being dispatched, and reads each step's scores
back to the host while the next one runs.

Each host span is a ``jax.profiler.TraceAnnotation`` as well, so that a
traced run puts it on the device trace's clock: ``window`` around the
whole loop, ``wait`` while no request is due, ``submit``, ``assemble``
(``MicroBatcher.next_batch``) and ``step`` (the compiled step, to
``block_until_ready``) in serve runs; ``dispatch``, ``wait`` (for the step
before the one just dispatched) and ``drain`` in bulk runs.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

#: the program's MicroBatcher times requests on time.monotonic; so does the
#: benchmark, so a due time handed to it as ``t_arrival`` means the same.
clock = time.monotonic


@dataclasses.dataclass
class ServeRecord:
    """What an open-loop window measured, in seconds on ``clock``."""
    due: np.ndarray          # (N,) absolute due times
    submitted: np.ndarray    # (N,) when each was handed to the batcher
    done: np.ndarray         # (N,) when its scores were ready; nan if never
    scores: np.ndarray       # (N,) its served score
    batches: list            # per batch: (t_assemble0, t_assemble1, t_done)
    batch_ids: list          # per batch: the real (non-pad) request ids
    window: tuple            # (open, close): first due .. last completion


@dataclasses.dataclass
class BulkRecord:
    n_steps: int
    samples: int
    window: tuple            # (open, close): first dispatch .. last ready
    outputs: list            # per step: (ring index, scores read back)


def warm_serve(system, stream, n_batches: int = 2) -> None:
    """Run full batches of the stream's first requests through the batcher
    and the step, so that every shape the window uses is compiled."""
    from repro.serve.serve_step import Request
    pad = {k: v[0] for k, v in stream.feats.items()}
    mb = system.batcher(pad)
    n = len(stream.due_s)
    for b in range(n_batches):
        for i in range(system.batch):
            j = (b * system.batch + i) % n
            mb.submit(Request(rid=j, features={k: v[j] for k, v in
                                               stream.feats.items()}))
        reqs, feats = mb.next_batch()
        jax.block_until_ready(system(feats))
        mb.complete(reqs)


def serve_open(system, stream) -> ServeRecord:
    """Offer the stream's requests at their due times; serve full batches."""
    from repro.serve.serve_step import Request
    n = len(stream.due_s)
    feats = stream.feats
    pad = {k: v[0] for k, v in feats.items()}
    mb = system.batcher(pad)
    B = system.batch
    submitted = np.full(n, np.nan)
    done = np.full(n, np.nan)
    scores = np.full(n, np.nan, np.float32)
    batches, batch_ids, pending = [], [], []
    with TraceAnnotation("window"):
        t0 = clock()
        due = t0 + stream.due_s
        i = 0
        while True:
            now = clock()
            if i < n and due[i] <= now:
                with TraceAnnotation("submit"):
                    while i < n and due[i] <= now:
                        mb.submit(Request(rid=i, features={
                            k: v[i] for k, v in feats.items()},
                            t_arrival=due[i]))
                        submitted[i] = now
                        i += 1
            if len(mb.queue) >= B or (i == n and mb.ready()):
                with TraceAnnotation("assemble"):
                    ta = clock()
                    reqs, fb = mb.next_batch()
                    tb = clock()
                with TraceAnnotation("step"):
                    out = system(fb)
                    jax.block_until_ready(out)
                    td = clock()
                mb.complete(reqs)
                ids = [r.rid for r in reqs]
                done[ids] = td
                batches.append((ta, tb, td))
                batch_ids.append(ids)
                pending.append(out)
                continue
            if i == n:
                break
            with TraceAnnotation("wait"):
                gap = due[i] - clock()
                if gap > 0:
                    time.sleep(gap)
        t_close = clock()
    for ids, out in zip(batch_ids, pending):
        scores[ids] = np.asarray(out)[:len(ids)]
    return ServeRecord(due=due, submitted=submitted, done=done, scores=scores,
                       batches=batches, batch_ids=batch_ids,
                       window=(t0, t_close))


def warm_bulk(system, ring) -> None:
    for batch in ring[:2]:
        jax.block_until_ready(system(batch))


def bulk_closed(system, ring, seconds: float) -> BulkRecord:
    """Score the ring's batches back to back for ``seconds``; the window
    closes when the last dispatched step is ready."""
    outputs = []
    with TraceAnnotation("window"):
        t0 = clock()
        n = 0
        while clock() - t0 < seconds:
            with TraceAnnotation("dispatch"):
                out = system(ring[n % len(ring)])
            if outputs:
                with TraceAnnotation("wait"):
                    # the step before is done: read its scores back while
                    # this one runs, so device memory stays flat
                    i, prev = outputs[-1]
                    outputs[-1] = (i, np.asarray(prev))
            outputs.append((n % len(ring), out))
            n += 1
        with TraceAnnotation("drain"):
            i, last = outputs[-1]
            outputs[-1] = (i, np.asarray(last))
        t1 = clock()
    return BulkRecord(n_steps=n, samples=n * system.batch, window=(t0, t1),
                      outputs=outputs)
