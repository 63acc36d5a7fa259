"""Named spans and scopes of the serving path, for the profiler.

Two kinds of name, both inert unless ``jax.profiler`` is recording:

* ``span(name, **args)`` is ``jax.profiler.TraceAnnotation``: a host
  span on the thread that enters it, on the same clock as the device
  planes of the trace.  Off, it costs about a microsecond.
* ``scope(name)`` is ``jax.named_scope``: it names the HLO ops that a
  traced function emits inside it (``metadata={op_name=".../qbs.bfs/..."}``),
  so a device op of a trace can be told to a phase of a lane program.
  It changes only metadata, never the compiled program's work.

``gc_spans()`` adds a ``qbs.gc`` span around every collection of Python's
garbage collector while it is entered.

Every name the program emits is listed below beside the per-layer
metric that reads it (the benchmark's readers, ``perfbench/``); a name
with no reader is cost with nothing to show for it.
"""
from __future__ import annotations

import contextlib
import gc

import jax

span = jax.profiler.TraceAnnotation
scope = jax.named_scope

# (host span, the metric that reads it)
SPANS = (
    ("qbs.router.route", "router.host_us_per_query"),
    ("qbs.planner.plan", "planner.host_ms_per_chunk"),
    ("qbs.service.dispatch", "dispatch.host_ms_per_chunk"),
    ("qbs.service.device_wait", "host.device_wait_share"),
    ("qbs.service.fetch", "fetch.host_ms_per_chunk"),
    ("qbs.stream.resolve", "resolve.host_ms_per_chunk"),
    ("qbs.gc", "gc.max_pause_ms"),
)

# (device scope, the metric that reads it)
SCOPES = (
    ("qbs.sketch", "general_lane.sketch_ms_per_query"),
    ("qbs.bfs", "general_lane.bfs_ms_per_query"),
    ("qbs.reverse", "general_lane.reverse_ms_per_query"),
    ("qbs.recover", "general_lane.recover_ms_per_query"),
    ("qbs.onesided.bfs", "onesided_lane.bfs_ms_per_query"),
    ("qbs.onesided.certify", "onesided_lane.certify_ms_per_query"),
    ("qbs.update", "update.device_ms_per_epoch"),
    ("qbs.update.relabel", "update.relabel_ms_per_epoch"),
)


@contextlib.contextmanager
def gc_spans():
    """While entered, each garbage collection runs inside a ``qbs.gc``
    span whose ``generation`` argument is the generation collected.  The
    hook leaves ``gc.callbacks`` on exit."""
    open_spans: list = []

    def hook(phase, info):
        if phase == "start":
            s = span("qbs.gc", generation=info["generation"])
            s.__enter__()
            open_spans.append(s)
        elif open_spans:
            open_spans.pop().__exit__(None, None, None)

    gc.callbacks.append(hook)
    try:
        yield
    finally:
        gc.callbacks.remove(hook)
        while open_spans:
            open_spans.pop().__exit__(None, None, None)
