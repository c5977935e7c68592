"""CPU tests of the benchmark harness at a size a test run holds.

Run from the repository root: ``python -m pytest -q bench/tests``.
The simulator's Pallas kernels run in interpret mode here.
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def small():
    """(cell, configuration, traffic) of the test-only 4x4 network."""
    from bench import run
    return ({"name": "test", "chips": 1},
            run.load_json(DATA / "dpsnn-4x4-test.json"),
            run.load_json(DATA / "steady-test.json"))
