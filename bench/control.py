#!/usr/bin/env python3
"""The comparison's two readings for one cell, on several seeds.

    python3 bench/control.py --workload <cell> --seconds 10 --seeds <n> ...

For each seed, in one process: one run of the cell as ``bench/run.py``
makes it (its compared numbers against the reference are the *sound*
reading), then the *control* -- the reference with its delayed-current
ring kept in bfloat16, the nearest precision below the float32 the
configuration states -- put in the simulator's place on the same
checkpointed segment starts and compared with the reference the same
way.  Prints one JSON line per seed.  The limits in a configuration
file lie between the largest sound reading and the smallest control
reading over the seeds (PERF.md gives both).  Needs the chip, as
``bench/run.py`` does; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import reference, run  # noqa: E402


def control_readings(config: dict, seg: int, inputs, devices) -> dict:
    """The control's readings on the sound run's checkpointed starts,
    its blocks on ``devices`` as the reference's."""
    import numpy as np
    model = reference.Model.from_config(config)
    syn = reference.device_synapses(model, reference.connectome(model),
                                    devices)
    low = reference.make_segment(model, seg, "bfloat16")
    outs = []
    for start, _, _ in inputs:
        state, spikes, events = low(start, syn)
        outs.append((start, {k: np.asarray(v) for k, v in state.items()},
                     {"spikes": float(spikes.sum()),
                      "events": float(events.sum())}))
    del syn
    return run.compare(config, seg, outs, devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    _, cell, config, traffic = run.load_cell(args.workload)
    peaks = run.load_json(run.BENCH / "peaks.json")
    seg = traffic["job"]["segment_steps"]
    import jax
    devices = jax.devices()[:cell["chips"]]
    for seed in args.seeds:
        ctx = run.run_cell(cell, config, traffic, seed, args.seconds, False,
                           peaks, t_start=time.perf_counter())
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "sound": ctx["readings"],
            "control": control_readings(config, seg, ctx["check_inputs"],
                                        devices),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
