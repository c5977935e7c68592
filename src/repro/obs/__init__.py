"""Spike observatory: device-side recording, disk spooling, analysis.

Three layers turn the fast kernel path into a scientifically usable
instrument (the paper family validates DPSNN by firing-rate
distributions and slow-wave/awake activity statistics):

  * ``record``  -- device-side recorder: per-step spike compaction
    (Pallas kernel or XLA fallback) into a bounded per-segment
    ``(step, global_neuron_id)`` event buffer carried in the scan state,
    with an explicit overflow-drop counter;
  * ``spool``   -- host-side async spooler: drains each segment's
    buffer into sharded append-only binary spike logs, with per-segment
    offsets recorded in the checkpoint manifest so resume replays
    deliver every event exactly once;
  * ``analysis``-- paper-family statistics from spooled logs (rate
    distributions, ISI CV, population rate, Up/Down segmentation) plus
    multi-run comparison, behind the ``repro.launch.analyze`` CLI.

A fourth layer, ``telemetry``, observes the *runtime* rather than the
spikes: a thread-aware host-side span tracer plus a structured
per-segment metrics stream (JSONL + Chrome-trace export via
``repro.perf.trace``), instrumenting every driver phase -- table build,
segment compute, counter read-back, checkpoint save/snapshot/D2H/write,
spool drain, restore/retile, straggler stalls.  Its spans are also
profiler annotations, so a ``jax.profiler`` trace holds them on the
device planes' clock.  Like recording, it is a pure observer: spike
trains and plastic weight checksums are bit-identical with tracing on
or off.
"""

from .record import (RecorderSpec, init_recorder_state, record_step,
                     recorder_spec, stacked_gid_maps, tile_gid_map)
from .spool import SpikeSpooler, load_events, read_header
from .telemetry import Telemetry, enable_json_logging, read_jsonl

__all__ = [
    "RecorderSpec", "init_recorder_state", "record_step", "recorder_spec",
    "stacked_gid_maps", "tile_gid_map", "SpikeSpooler", "load_events",
    "read_header", "Telemetry", "enable_json_logging", "read_jsonl",
]
