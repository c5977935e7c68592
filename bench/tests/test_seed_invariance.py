"""Every --seed of a configuration runs the same connectome and the
same compiled shapes; only the dynamics differ."""

import jax
import numpy as np

from bench import run


def test_seeds_share_connectome_and_program(small, tmp_path):
    from repro.runtime import build_sim_driver
    from repro.runtime.sim_driver import sim_fingerprint
    _, config, traffic = small
    drivers, spikes = [], []
    for seed in (5, 2 ** 31 + 9):
        d = build_sim_driver(run.job_spec(config, traffic, seed,
                                          str(tmp_path / str(seed))))
        out = d.run(40)
        spikes.append([m["d_spikes"] for m in out["metrics"]])
        drivers.append(d)
    a, b = drivers
    assert a.table_stats["n_synapses"] == b.table_stats["n_synapses"] \
        == config["n_synapses"]
    assert a.storage == b.storage
    shapes = [[x.shape for x in jax.tree.leaves(d.tables)]
              for d in drivers]
    assert shapes[0] == shapes[1]
    assert sim_fingerprint(a.dist_cfg, 5, a.recorder, a.storage) \
        == sim_fingerprint(b.dist_cfg, 5, b.recorder, b.storage)
    for k in ("tgt", "w", "dslot", "nnz"):
        assert np.array_equal(np.asarray(a.tables.local[k]),
                              np.asarray(b.tables.local[k]))
    assert spikes[0] != spikes[1]
