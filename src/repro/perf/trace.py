"""Trace exporters for the runtime telemetry stream.

Thin, dependency-free views over ``repro.obs.telemetry`` records:

  * **Chrome trace** (``chrome://tracing`` / Perfetto / speedscope):
    every span becomes a complete ``"ph": "X"`` duration event on its
    emitting thread, so the async checkpoint writer's D2H/file-write
    lanes render *under* the main thread's segment lane and the
    double-buffered overlap is visible instead of inferred; structured
    events become instant (``"ph": "i"``) markers.
  * **jax.profiler wrapper**: the opt-in deep profile
    (``repro.launch.sim --trace-dir``) capturing XLA/TFRT internals and
    the device planes; an enabled tracer's spans land in it as profiler
    annotations, on the same clock.  (The raw JSONL record stream is
    ``Telemetry.flush_jsonl``.)
"""

from __future__ import annotations

import contextlib
import json
import os
from typing import List, Optional

from ..obs.telemetry import FORMAT, Telemetry

__all__ = ["to_chrome_trace", "write_chrome_trace", "jax_profiler_trace"]


def to_chrome_trace(records: List[dict], pid: Optional[int] = None) -> dict:
    """Convert telemetry records to the Chrome Trace Event JSON format.

    Spans map to complete events (``ph: "X"``; microsecond ``ts`` /
    ``dur`` relative to the tracer epoch), events to instant markers
    scoped to their thread, and each thread gets a ``thread_name``
    metadata event so the viewer shows ``MainThread`` vs the writer
    daemons by name.
    """
    pid = os.getpid() if pid is None else pid
    trace_events: List[dict] = []
    thread_names = {}
    for rec in records:
        if rec.get("type") == "span":
            thread_names.setdefault(rec["tid"], rec["thread"])
            args = dict(rec.get("attrs", {}))
            if rec.get("parent"):
                args["parent"] = rec["parent"]
            args["depth"] = rec["depth"]
            trace_events.append({
                "name": rec["name"], "cat": "span", "ph": "X",
                "ts": rec["t0"] * 1e6, "dur": rec["dur"] * 1e6,
                "pid": pid, "tid": rec["tid"], "args": args,
            })
        elif rec.get("type") in ("event", "metrics"):
            payload = {k: v for k, v in rec.items()
                       if k not in ("type", "kind", "t")}
            trace_events.append({
                "name": rec["kind"], "cat": rec["type"], "ph": "i",
                "ts": rec["t"] * 1e6, "pid": pid, "tid": 0, "s": "p",
                "args": payload,
            })
    for tid, name in sorted(thread_names.items()):
        trace_events.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": name},
        })
    return {"traceEvents": trace_events, "displayTimeUnit": "ms",
            "otherData": {"format": FORMAT}}


def write_chrome_trace(source, path: str) -> str:
    """Write a Chrome trace JSON for ``source`` (a ``Telemetry`` tracer
    or a raw record list); returns ``path``.  Load it in
    ``chrome://tracing`` or https://ui.perfetto.dev."""
    records = source.records() if isinstance(source, Telemetry) else source
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(to_chrome_trace(records), f, indent=1)
    return path


@contextlib.contextmanager
def jax_profiler_trace(trace_dir: Optional[str]):
    """Opt-in ``jax.profiler.trace`` wrapper (``--trace-dir``).

    ``None`` is a no-op, so call sites wrap unconditionally.  The
    profile (TensorBoard / Perfetto protobuf under ``trace_dir``)
    captures the device and XLA internals, and the spans of every
    enabled ``Telemetry`` as host annotations on the same clock.
    """
    if trace_dir is None:
        yield
        return
    import jax
    os.makedirs(trace_dir, exist_ok=True)
    with jax.profiler.trace(trace_dir):
        yield
