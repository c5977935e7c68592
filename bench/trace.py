"""Reduce a JAX profiler trace (``.xplane.pb``) to device times.

The benchmark brackets its measured window with a host annotation
(``WINDOW``); everything here is clipped to that annotation's interval.
From the TPU device planes' ``XLA Ops`` lines it takes:

* the device self time of each operation, averaged over the device
  planes -- its time less that of the
  operations nested in it, as the body of a ``while`` is in the loop --
  by name, read from the HLO text the event carries with the numeric
  suffix XLA adds stripped (``%fusion.12 = ...`` -> ``fusion``; a Pallas
  kernel keeps the name it was given, such as ``synaptic_accum``);
* the busy time: the union of the intervals in which any operation ran,
  averaged over the device planes (``device_busy`` gives each plane's
  intervals);
* the idle gaps between busy intervals, each named after the host event
  (``<thread>:<event>``) that overlaps it most -- what the host was
  doing while the device waited.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_NAME = re.compile(r"%?([\w.\-]+)")
_SUFFIX = re.compile(r"\.\d+$")


def op_name(name: str) -> str:
    m = _NAME.match(name)
    return _SUFFIX.sub("", m.group(1) if m else name)


def _self_times(spans: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds by operation name, each event less the events nested in
    it on the same line."""
    out: Dict[str, float] = {}
    stack: List[list] = []              # [end, name, self_ns]

    def close(item):
        out[item[1]] = out.get(item[1], 0.0) + item[2] * 1e-9

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack and e <= stack[-1][0]:
            stack[-1][2] -= e - s
        else:
            while stack:
                close(stack.pop())
        stack.append([e, name, e - s])
    while stack:
        close(stack.pop())
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def load(path: str):
    """A trace file, gzipped (``.gz``) or not."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def window_of(profile) -> Tuple[float, float]:
    """(start_ns, end_ns) of the benchmark's window annotation."""
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    raise ValueError(f"no {WINDOW!r} annotation in the trace")


def _device_ops(profile, t0: float, t1: float
                ) -> List[List[Tuple[float, float, str]]]:
    """Per TPU device plane with an operation inside ``[t0, t1)``, its
    operations ``(start_ns, end_ns, name)`` clipped to it."""
    out = []
    for plane in profile.planes:
        if not _DEVICE.match(plane.name):
            continue
        spans = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s = max(ev.start_ns, t0)
                e = min(ev.start_ns + ev.duration_ns, t1)
                if e > s:
                    spans.append((s, e, op_name(ev.name)))
        if spans:
            out.append(spans)
    return out


def device_busy(profile, t0: float, t1: float
                ) -> List[List[Tuple[float, float]]]:
    """Per TPU device plane with an operation inside ``[t0, t1)``, the
    sorted, disjoint intervals (ns) in which one ran, clipped to it."""
    return [_union([(s, e) for s, e, _ in spans])
            for spans in _device_ops(profile, t0, t1)]


def reduce(profile, top: int = 10) -> Optional[Dict]:
    """Device times of the window, or None when no TPU plane holds an
    operation inside it."""
    t0, t1 = window_of(profile)
    per_device = []
    op_s: Dict[str, float] = {}
    for spans in _device_ops(profile, t0, t1):
        per_device.append(_union([(s, e) for s, e, _ in spans]))
        for k, v in _self_times(spans).items():
            op_s[k] = op_s.get(k, 0.0) + v
    if not per_device:
        return None
    busy = [sum(e - s for s, e in u) * 1e-9 for u in per_device]
    gaps = _idle_gaps(profile, per_device[0], t0, t1, top)
    ops = sorted(op_s.items(), key=lambda kv: -kv[1])
    n_dev = len(per_device)
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": sum(busy) / n_dev,
        "op_s": {k: v / n_dev for k, v in op_s.items()},
        "device_ops": [[k, v / n_dev] for k, v in ops[:top]],
        "idle_gaps": gaps,
    }


def _idle_gaps(profile, busy, t0: float, t1: float,
               top: int) -> List[list]:
    """The ``top`` longest idle gaps, longest first."""
    edges = [t0] + [x for s, e in busy for x in (s, e)] + [t1]
    holes = sorted(((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]),
                   key=lambda h: h[0] - h[1])[:top]
    host = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != WINDOW and ev.duration_ns > 0:
                    host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 f"{line.name}:{ev.name}"))
    host.sort()
    out = []
    for s, e in holes:
        best, best_key = "unattributed", None
        for hs, he, name in host:
            if hs >= e:
                break
            overlap = min(he, e) - max(hs, s)
            if overlap <= 0:
                continue
            # most overlap first, then the shortest (innermost) event
            key = (overlap, -(he - hs))
            if best_key is None or key > best_key:
                best, best_key = name, key
        out.append([best, (e - s) * 1e-9])
    return out
