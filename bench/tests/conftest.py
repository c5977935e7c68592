"""CPU tests of the benchmark harness at a size a test run holds.

Run from the repository root: ``python -m pytest -q bench/tests``.
The simulator's Pallas kernels run in interpret mode here, on four
host devices, so that a 2x2 tiling runs as it does on four chips.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# before jax is first imported: it fixes the device count then
os.environ["XLA_FLAGS"] = " ".join(
    [os.environ.get("XLA_FLAGS", ""),
     "--xla_force_host_platform_device_count=4"]).strip()

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


# synapses the simulator realizes of the 4x4 test network on each
# tiling: on 2x2 each tile draws from a stream of its own
N_SYNAPSES = {(1, 1): 625375, (2, 2): 625972}


def network(tiles=(1, 1)):
    """(cell, configuration, traffic) of the test-only 4x4 network on
    ``tiles``, one chip per tile."""
    from bench import run
    config = run.load_json(DATA / "dpsnn-4x4-test.json")
    return ({"name": "test", "chips": tiles[0] * tiles[1]},
            dict(config, tiles=list(tiles), n_synapses=N_SYNAPSES[tiles]),
            run.load_json(DATA / "steady-test.json"))


@pytest.fixture
def small():
    """The test network on one tile."""
    return network()


@pytest.fixture
def four_tiles():
    """The test network on 2x2 tiles."""
    return network((2, 2))


@pytest.fixture(params=[(1, 1), (2, 2)], ids=["1x1", "2x2"])
def tiled(request):
    """The test network on one tile and on 2x2 tiles."""
    return network(request.param)
