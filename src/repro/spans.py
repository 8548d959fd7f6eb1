"""Profiler spans of the trigger server and its per-stage counters.

Every span is a ``jax.profiler.TraceAnnotation``: while a profiler
session runs it lands in the same trace as the device operations, on
the host thread that ran it and on the same clock; with no session
running it costs about a microsecond. Spans are opened once per launch
or per stage, never once per event. Keyword arguments given to a span
(or set on it before it closes) become the trace event's stats.

Lane stages (``serving/streaming.py``; the deadline loop in
``serving/replica.py`` records ``PACK``, ``PIPELINE``, ``FETCH`` and
``RELEASE``) also add their duration to the lane's ``StageCounters``,
which are always on: ``ServingStats.budget()`` reports them, and a
stall longer than any traced window still shows in a stage's
``max_s``. Pipeline spans (``core/pipeline.py``) are spans only.
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

# whether a profiler session is recording (a span opened with none
# running records nothing)
_tracing = TraceAnnotation.is_enabled

# -- lane stages ---------------------------------------------------------
# launcher: blocked on a free in-flight slot
SLOT_WAIT = "trigger.slot_wait"
# launcher (deadline loop: dispatch worker): queue sweep, copy into the
# input ring, device_put; stats events (launch width), queued (queue
# depth after the sweep), and in the streaming loop for an edge model
# (an event feed named edge_mask) edges, the real edges staged
PACK = "trigger.pack"
# dispatch worker: the call into the served pipeline; stat events
PIPELINE = "trigger.pipeline"
# harvester: waiting on the dispatch worker's future
RESULT_WAIT = "trigger.result_wait"
# harvester: polling the outputs' is_ready
DEVICE_WAIT = "trigger.device_wait"
# harvester: device-to-host copy of the outputs into the output ring
FETCH = "trigger.fetch"
# harvester: per-event unflatten, monitor tap, in-order release; stats
# events, held (results the releaser holds back for an earlier event)
RELEASE = "trigger.release"
LANE_STAGES = (SLOT_WAIT, PACK, PIPELINE, RESULT_WAIT, DEVICE_WAIT,
               FETCH, RELEASE)

# -- pipeline spans ------------------------------------------------------
# CompiledPipeline: zero-padding a call up to the microbatch multiple
PIPELINE_PAD = "trigger.pipeline.pad"
# CompiledPipeline: slicing and dispatching one chunk; stat rows
PIPELINE_CHUNK = "trigger.pipeline.chunk"
# CompiledPipeline: joining the chunks' outputs
PIPELINE_CONCAT = "trigger.pipeline.concat"
# RaggedPipeline: pack_events, the launch plan, bin_pack and the masks;
# stats events, bins (launched), rows (real hits; bin fill is rows over
# bins times capacity)
RAGGED_PACK = "trigger.ragged.pack"
# RaggedPipeline: the call into the packed executable
RAGGED_LAUNCH = "trigger.ragged.launch"
# RaggedPipeline: reading the outputs back, which waits for the device
RAGGED_FETCH = "trigger.ragged.fetch"
# RaggedPipeline: unpack_binned and the final concatenate
RAGGED_UNPACK = "trigger.ragged.unpack"


class StageCounters:
    """Count, total and largest duration (s) of each stage of one lane.
    Stages of one lane run on several threads, so updates take a
    lock."""

    def __init__(self):
        self._lock = threading.Lock()
        self._c: dict[str, list] = {}

    def add(self, name: str, secs: float):
        with self._lock:
            c = self._c.get(name)
            if c is None:
                self._c[name] = [1, secs, secs]
            else:
                c[0] += 1
                c[1] += secs
                if secs > c[2]:
                    c[2] = secs

    def snapshot(self) -> dict[str, dict]:
        """``{stage: {"count", "total_s", "max_s"}}`` of the stages
        recorded so far."""
        with self._lock:
            return {k: {"count": n, "total_s": t, "max_s": m}
                    for k, (n, t, m) in self._c.items()}


class stage:
    """One lane stage: a profiler span named ``name`` with ``stats``
    as its event stats, whose duration is added to ``counters``.
    ``set(**stats)`` adds stats known only at the stage's end. With no
    profiler running only the counters are kept."""

    __slots__ = ("_ann", "_counters", "_name", "_t")

    def __init__(self, name: str, counters: StageCounters, **stats):
        self._ann = TraceAnnotation(name, **stats) if _tracing() else None
        self._counters = counters
        self._name = name

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t = time.perf_counter()
        return self

    def set(self, **stats):
        if self._ann is not None:
            self._ann.set_metadata(**stats)

    def __exit__(self, exc_type, exc, tb):
        secs = time.perf_counter() - self._t
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        self._counters.add(self._name, secs)
        return False
