"""The work a static DPSNN step requires, counted from counts alone.

What delivery and the step *must* do, whatever implements them: the
operations and bytes are functions of the synaptic events delivered,
the neurons updated, the steps taken and the storage dtypes the
configuration states.  Nothing here looks at how the program does it
(no HLO cost analysis, no padded rows, no one-hot contraction), so two
implementations of delivery doing the same events are charged the
same work, and a roofline share can only rise by doing that work
faster.

Per synaptic event (one spike crossing one synapse): 2 FLOP (the
weight times the spike, added into the target's ring entry) and one
read of the table entry (target id, weight and delay slot in their
storage dtypes).  Per step: one read and one write of the ring slot
being consumed and cleared (``n_neurons`` float32 each).  Per neuron
per step, the leaky integrate-and-fire update with spike-frequency
adaptation: ``LIF_FLOPS`` operations on ``LIF_BYTES`` bytes (read
``v``, ``c``, ``refrac`` and the input current; write ``v``, ``c``,
``refrac``).
"""

from __future__ import annotations

import dataclasses

import numpy as np

FLOPS_PER_EVENT = 2
# v_rest + (v - v_rest) * decay + I - g * c * dt: 6; threshold compare,
# two selects for reset and refractoriness: 3; c * decay + alpha * s: 3;
# refractory countdown: 2
LIF_FLOPS = 14
LIF_BYTES = 4 * 4 + 3 * 4
RING_BYTES = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float


def entry_bytes(tgt_dtype: str, weight_dtype: str,
                dslot_dtype: str = "int8") -> int:
    """Bytes of one synapse-table entry in its storage dtypes."""
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)
    return sum(np.dtype(d).itemsize
               for d in (tgt_dtype, weight_dtype, dslot_dtype))


def delivery(events: float, neurons: int, steps: int,
             entry_size: int) -> Work:
    """Required work of delivering ``events`` over ``steps`` steps."""
    return Work(flops=FLOPS_PER_EVENT * events,
                bytes=events * entry_size
                + steps * 2 * neurons * RING_BYTES)


def step(events: float, neurons: int, steps: int,
         entry_size: int) -> Work:
    """Required work of whole steps: delivery plus the neuron update."""
    d = delivery(events, neurons, steps, entry_size)
    return Work(flops=d.flops + steps * neurons * LIF_FLOPS,
                bytes=d.bytes + steps * neurons * LIF_BYTES)


def roofline_seconds(work: Work, peak: dict):
    """(least seconds at the chip's peaks, which peak bounds it)."""
    t_flops = work.flops / peak["flops_per_s"]
    t_bytes = work.bytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
