"""The simulator's own spans against the device trace.

An enabled ``repro.obs.telemetry.Telemetry`` opens each span as a
profiler annotation on the thread that emits it, so the ``.xplane.pb``
of a traced run holds the program's phases on the device planes' clock.
``idle_by_span`` puts each instant of the window in which the device
ran nothing down to the innermost program span open on the main thread
(the host line that carries the benchmark's window annotation) at that
instant.  Idle time with no span open, or inside the outer ``segment``
span but none of its children, is unattributed: the program does not
say what the host was doing.  ``context`` gives the two entries of a
metric reader's ``ctx`` that the span metrics read: ``setup_spans``
and ``span_idle``; ``bench/run.py:run_cell`` calls it in a traced run,
on the profile it loads for ``bench/trace.py``.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional, Set, Tuple

from bench import trace

# the driver's span around one whole segment: its self time (the
# per-segment hook, bookkeeping, the wait on the segment's metrics)
# names no phase
OUTER = "segment"

Interval = Tuple[float, float]


def say(msg: str):
    print(f"[spans] {msg}", file=sys.stderr, flush=True)


def innermost(spans: List[Tuple[float, float, str]], t0: float,
              t1: float) -> List[Tuple[float, float, Optional[str]]]:
    """``[t0, t1)`` cut into pieces, each labelled with the innermost of
    the nested ``(start, end, name)`` spans of one thread open over it
    (``None`` where none is)."""
    out: List[Tuple[float, float, Optional[str]]] = []
    stack: List[Tuple[float, str]] = []          # (end, name)
    cur = t0

    def emit(upto: float):
        nonlocal cur
        if upto > cur:
            out.append((cur, upto, stack[-1][1] if stack else None))
            cur = upto

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        # a child never outlives its parent, whatever the rounding
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(t1)
    return out


def idle_of(busy: List[Interval], t0: float, t1: float) -> List[Interval]:
    """The holes in sorted, disjoint ``busy`` intervals within
    ``[t0, t1)``."""
    edges = [t0] + [x for s, e in busy for x in (s, e)] + [t1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def split(holes: List[Interval],
          pieces: List[Tuple[float, float, Optional[str]]]
          ) -> List[Dict[Optional[str], float]]:
    """Per hole, its length by the label of the pieces it overlaps
    (``pieces`` sorted and covering every hole)."""
    out = []
    i = 0
    for s, e in holes:
        while i < len(pieces) and pieces[i][1] <= s:
            i += 1
        part: Dict[Optional[str], float] = {}
        j = i
        while j < len(pieces) and pieces[j][0] < e:
            ov = min(e, pieces[j][1]) - max(s, pieces[j][0])
            if ov > 0:
                part[pieces[j][2]] = part.get(pieces[j][2], 0.0) + ov
            j += 1
        out.append(part)
    return out


def gap_name(part: Dict[Optional[str], float]) -> str:
    """The span that holds most of one idle gap."""
    label = max(part, key=part.get) if part else None
    return "unattributed" if label in (None, OUTER) else label


def attribute(busy_per_device: List[List[Interval]],
              spans: List[Tuple[float, float, str]], t0: float, t1: float,
              top: int = 10) -> Dict:
    """Idle seconds of the window by innermost main-thread span,
    averaged over the devices; times in nanoseconds, results in
    seconds."""
    pieces = innermost(spans, t0, t1)
    by_span: Dict[str, float] = {}
    unattributed = 0.0
    gaps = []
    for d, busy in enumerate(busy_per_device):
        holes = idle_of(busy, t0, t1)
        parts = split(holes, pieces)
        for part in parts:
            for label, ns in part.items():
                if label is None or label == OUTER:
                    unattributed += ns
                else:
                    by_span[label] = by_span.get(label, 0.0) + ns
        if d == 0:
            gaps = sorted(([gap_name(part), (e - s) * 1e-9]
                           for (s, e), part in zip(holes, parts)),
                          key=lambda g: -g[1])[:top]
    n = max(len(busy_per_device), 1)
    by_span = {k: v * 1e-9 / n for k, v in by_span.items()}
    unattributed *= 1e-9 / n
    return {"idle_s": sum(by_span.values()) + unattributed,
            "by_span": by_span, "unattributed_s": unattributed,
            "gaps": gaps}


def main_line_spans(profile, names: Set[str]
                    ) -> List[Tuple[float, float, str]]:
    """``(start_ns, end_ns, name)`` of the program spans (events named
    in ``names``) on the host line that carries the window annotation."""
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            if any(ev.name == trace.WINDOW for ev in events):
                return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in events if ev.name in names]
    raise ValueError(f"no {trace.WINDOW!r} annotation in the trace")


def idle_by_span(profile, names: Set[str], top: int = 10) -> Optional[Dict]:
    """The window's device-idle time by program span, or None when no
    TPU plane holds an operation inside the window."""
    t0, t1 = trace.window_of(profile)
    busy = trace.device_busy(profile, t0, t1)
    if not busy:
        return None
    return attribute(busy, main_line_spans(profile, names), t0, t1, top)


def context(tel, window_t0: float, profile=None) -> Dict:
    """``setup_spans`` (the tracer's spans that ended before the window
    opened at ``window_t0``, a ``perf_counter`` reading: the segment
    whose hook opens the window is the window's) and ``span_idle``
    (``idle_by_span`` of ``profile``, None without one)."""
    records = tel.spans()
    out = {"setup_spans": [s for s in records
                           if s["t0"] + s["dur"] <= window_t0 - tel.epoch],
           "span_idle": None}
    if profile is not None:
        out["span_idle"] = idle_by_span(profile,
                                        {s["name"] for s in records})
        for name, s in (out["span_idle"] or {}).get("gaps", []):
            say(f"idle gap {s * 1e3:.3f} ms in {name}")
    return out
