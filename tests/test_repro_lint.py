"""repro-lint analyzer: every pass catches its seeded violation, stays
silent on the clean twin, and the live tree lints clean.

The violation fixtures live as source strings (written to temp files
per test), NOT as real modules -- CI lints ``tests/`` too, and these
snippets must never count as repo code.
"""

import subprocess
import sys
import threading

import pytest

from repro.analysis import (ALL_CHECKERS, DeprecatedApiChecker,
                            DonationChecker,
                            DtypeContractsChecker, MetaDriftChecker,
                            Module, PallasGeometryChecker, Project,
                            PytreeAuxChecker, TracerPurityChecker)
from repro.checkpoint.store import (AsyncWriterThread,
                                    set_thread_asserts,
                                    thread_asserts_enabled)

SRC_ROOT = __file__.rsplit("/tests/", 1)[0] + "/src"


def run_checker(checker, sources, paths=None):
    """Lint in-memory sources; returns the surviving findings."""
    mods = []
    for i, src in enumerate(sources):
        path = (paths[i] if paths else f"fixture_{i}.py")
        mods.append(Module(path, source=src))
    return Project(mods).run([checker()])


def assert_flags(checker, bad, clean, paths=None):
    """The pass must flag the seeded violation and stay silent on the
    clean twin."""
    hits = run_checker(checker, [bad], paths)
    assert hits, f"{checker.name} missed its seeded violation"
    assert all(f.check == checker.name for f in hits)
    quiet = run_checker(checker, [clean], paths)
    assert not quiet, f"{checker.name} false-positive on clean twin: " \
        f"{[str(f) for f in quiet]}"
    return hits


# ---------------------------------------------------------------------------
# tracer-purity
# ---------------------------------------------------------------------------

TRACED_RNG_BAD = '''
import numpy as np
import jax

def body(carry, x):
    noise = np.random.default_rng(0).normal()   # host RNG at trace time
    return carry + noise, x

def run(xs):
    return jax.lax.scan(body, 0.0, xs)
'''

TRACED_RNG_CLEAN = '''
import jax
import jax.numpy as jnp

def body(carry, x):
    k, c = carry
    k, sub = jax.random.split(k)
    noise = jax.random.normal(sub, ())
    return (k, c + noise), x

def run(key, xs):
    return jax.lax.scan(body, (key, 0.0), xs)
'''


def test_tracer_purity_flags_host_rng_in_scan_body():
    hits = assert_flags(TracerPurityChecker, TRACED_RNG_BAD,
                        TRACED_RNG_CLEAN)
    assert any("numpy.random" in f.message for f in hits)


TRACED_BRANCH_BAD = '''
import jax
import jax.numpy as jnp

@jax.jit
def step(v):
    total = jnp.sum(v)
    if total > 0:                      # tracer has no truth value
        total = total * 2.0
    return total
'''

TRACED_BRANCH_CLEAN = '''
import jax
import jax.numpy as jnp

@jax.jit
def step(v):
    total = jnp.sum(v)
    if v.shape[0] > 0:                 # static shape check is fine
        total = jnp.where(total > 0, total * 2.0, total)
    return total
'''


def test_tracer_purity_flags_python_branch_on_traced_value():
    hits = assert_flags(TracerPurityChecker, TRACED_BRANCH_BAD,
                        TRACED_BRANCH_CLEAN)
    assert any("`if`" in f.message for f in hits)


TRACED_IO_BAD = '''
import jax

def inner(x):
    print("step", x)                   # host I/O inside jit
    return x * 2

@jax.jit
def step(x):
    return inner(x)
'''


def test_tracer_purity_follows_the_call_graph():
    # `inner` is only traced *transitively* (jit body calls it)
    hits = run_checker(TracerPurityChecker, [TRACED_IO_BAD])
    assert any("print" in f.message and f.line == 5 for f in hits), \
        [str(f) for f in hits]


def test_tracer_purity_flags_unseeded_rng_anywhere():
    bad = "import numpy as np\nx = np.random.rand(4)\n"
    clean = "import numpy as np\nx = np.random.default_rng(7).random(4)\n"
    hits = assert_flags(TracerPurityChecker, bad, clean)
    assert "hidden" in hits[0].message


def test_tracer_purity_allows_host_timing_outside_trace():
    clean = '''
import time

def wall(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0
'''
    assert not run_checker(TracerPurityChecker, [clean])


TELEMETRY_SPAN_BAD = '''
import time

import jax
from repro.obs import telemetry

def body(carry, x):
    with telemetry.span("inner"):      # span inside the scan body
        t0 = time.monotonic()          # clock reads at trace time
        carry = carry + x
    return carry, t0

def run(xs):
    return jax.lax.scan(body, 0.0, xs)
'''

TELEMETRY_SPAN_CLEAN = '''
import jax
from repro.obs import telemetry

def body(carry, x):
    return carry + x, x

def run(xs):
    with telemetry.span("segment"):    # wraps the jitted call site
        out = jax.jit(lambda: jax.lax.scan(body, 0.0, xs))()
    return out
'''


def test_tracer_purity_flags_telemetry_span_in_scan_body():
    # the pure-observer contract, enforced statically: a span (or raw
    # host clock) inside a traced closure measures trace time, not the
    # compiled step -- both must be flagged; the same span wrapped
    # around the jit call site is the documented idiom and stays quiet
    hits = assert_flags(TracerPurityChecker, TELEMETRY_SPAN_BAD,
                        TELEMETRY_SPAN_CLEAN)
    assert any("telemetry repro.obs.telemetry.span" in f.message
               for f in hits), [str(f) for f in hits]
    assert any("time.monotonic" in f.message for f in hits), \
        [str(f) for f in hits]


# ---------------------------------------------------------------------------
# dtype-bounds
# ---------------------------------------------------------------------------

F64_BAD = '''
import numpy as np

def fan(n):
    return np.zeros(n, dtype=np.float64)
'''

F64_CLEAN = '''
import numpy as np

def fan(n):
    return np.zeros(n, dtype=np.float32)
'''


def test_dtype_flags_float64_in_core_only():
    path = "src/repro/core/fixture.py"
    hits = assert_flags(DtypeContractsChecker, F64_BAD, F64_CLEAN,
                        paths=[path])
    assert "f32-first" in hits[0].message
    # the same source outside core//kernels/ is not flagged
    assert not run_checker(DtypeContractsChecker, [F64_BAD],
                           paths=["src/repro/obs/fixture.py"])


ACCUM_BAD = '''
import jax.numpy as jnp

def total(w):
    return jnp.sum(w.astype(jnp.bfloat16))
'''

ACCUM_CLEAN = '''
import jax.numpy as jnp

def total(w):
    return jnp.sum(w.astype(jnp.float32))
'''


def test_dtype_flags_accumulation_in_storage_dtype():
    hits = assert_flags(DtypeContractsChecker, ACCUM_BAD, ACCUM_CLEAN,
                        paths=["src/repro/obs/fixture.py"])
    assert "storage dtype" in hits[0].message


INT16_BAD = '''
from repro.core.synapses import TableStorage

st = TableStorage(tgt_dtype="int16", weight_dtype="bfloat16",
                  accum_dtype="float32", cap_local=4, halo_caps=())
'''


def test_dtype_flags_handmade_int16_storage():
    hits = run_checker(DtypeContractsChecker, [INT16_BAD],
                       paths=["src/repro/obs/fixture.py"])
    assert any("hand-built" in f.message for f in hits)
    # inside core/synapses.py itself (where the bound lives) it's fine
    assert not run_checker(DtypeContractsChecker, [INT16_BAD],
                           paths=["src/repro/core/synapses.py"])


def test_dtype_int16_bound_holds_for_committed_configs():
    """The live cross-check: every committed grid x law x tiling that
    selects int16 target ids keeps n_local under 2**15 (runs the real
    constructors, so the check can't drift from the code)."""
    from repro.configs.snn import CASES, reduced_case
    from repro.analysis.dtype_contracts import _TILINGS
    cases = dict(CASES)
    cases["reduced"] = reduced_case()
    checked = 0
    for case in cases.values():
        for ty, tx in _TILINGS:
            if case.grid[0] % ty or case.grid[1] % tx:
                continue
            spec = case.engine_config(ty, tx).spec()
            st = spec.storage()
            if st.tgt_dtype == "int16":
                assert spec.n_local < 2 ** 15, (case.name, ty, tx)
                checked += 1
    assert checked > 0


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------

DONATION_BAD = '''
import jax
import jax.numpy as jnp

def run(state, xs):
    sim = jax.jit(lambda s, x: s + x, donate_argnums=(0,))
    out = sim(state, xs)
    return out + jnp.sum(state)        # state's buffer was donated
'''

DONATION_CLEAN = '''
import jax
import jax.numpy as jnp

def run(state, xs):
    sim = jax.jit(lambda s, x: s + x, donate_argnums=(0,))
    state = sim(state, xs)             # canonical rebinding
    return state + jnp.sum(state)
'''


def test_donation_flags_read_after_donating_call():
    hits = assert_flags(DonationChecker, DONATION_BAD, DONATION_CLEAN)
    assert "`state`" in hits[0].message


DONATION_FACTORY_BAD = '''
import jax

def make_sim(n):
    def step(s, x):
        return s + x
    return jax.jit(step, donate_argnums=(0,))

def drive(state, xs):
    sim = make_sim(3)
    new = sim(state, xs)
    return state                       # read through the factory's donation
'''


def test_donation_tracks_jit_factories():
    hits = run_checker(DonationChecker, [DONATION_FACTORY_BAD])
    assert any(f.line == 12 for f in hits), [str(f) for f in hits]


DONATION_BRANCH_CLEAN = '''
import jax

def drive(state, xs, timed):
    sim = jax.jit(lambda s, x: s + x, donate_argnums=(0,))
    state = sim(state, xs)
    if timed:
        state = sim(state, xs)         # rebinding inside the branch
    return state
'''


def test_donation_branches_merge_without_false_positives():
    assert not run_checker(DonationChecker, [DONATION_BRANCH_CLEAN])


# ---------------------------------------------------------------------------
# meta-drift
# ---------------------------------------------------------------------------

META_BAD = '''
from repro.checkpoint.store import refuse_meta_drift

class SimDriver:
    def _meta(self):
        return {"grid": self.grid, "law": self.law, "seed": self.seed,
                "table_realization": 3, "radius": self.radius,
                "cap_headroom": self.cap_headroom}

    def _restore(self, meta):
        refuse_meta_drift(
            meta, self._meta(),
            ("grid", "law", "radius", "seed", "table_realization"),
            "dir")
'''

META_CLEAN = META_BAD.replace(
    '("grid", "law", "radius", "seed", "table_realization")',
    '("grid", "law", "radius", "seed", "table_realization", '
    '"cap_headroom")')


def test_meta_drift_flags_unvalidated_manifest_key():
    hits = assert_flags(MetaDriftChecker, META_BAD, META_CLEAN,
                        paths=["src/repro/runtime/sim_driver.py"])
    assert any("cap_headroom" in f.message for f in hits)


def test_meta_drift_requires_identity_keys_refused():
    src = '''
class SimDriver:
    def _meta(self):
        return {"grid": 1}
'''
    hits = run_checker(MetaDriftChecker, [src],
                       paths=["src/repro/runtime/sim_driver.py"])
    assert any("identity key 'seed'" in f.message for f in hits)


def test_meta_drift_storage_fields_roundtrip():
    src = '''
import dataclasses

@dataclasses.dataclass(frozen=True)
class TableStorage:
    tgt_dtype: str
    weight_dtype: str

    def meta(self):
        return {"tgt_dtype": self.tgt_dtype}   # weight_dtype missing
'''
    hits = run_checker(MetaDriftChecker, [src],
                       paths=["src/repro/core/synapses.py"])
    assert any("weight_dtype" in f.message for f in hits)


# ---------------------------------------------------------------------------
# pytree-aux
# ---------------------------------------------------------------------------

PYTREE_BAD = '''
import jax

@jax.tree_util.register_pytree_node_class
class Tables:
    def __init__(self, local, meta):
        self.local, self.meta = local, meta

    def tree_flatten(self):
        return (self.local,), {"meta": self.meta}   # dict aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux["meta"])
'''

PYTREE_CLEAN = '''
import jax

@jax.tree_util.register_pytree_node_class
class Tables:
    def __init__(self, local, storage):
        self.local, self.storage = local, storage

    def tree_flatten(self):
        return (self.local,), self.storage          # frozen dataclass

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux)
'''


def test_pytree_aux_flags_mutable_aux():
    hits = assert_flags(PytreeAuxChecker, PYTREE_BAD, PYTREE_CLEAN)
    assert "dict literal" in hits[0].message


# ---------------------------------------------------------------------------
# pallas-geometry
# ---------------------------------------------------------------------------

GEOMETRY_BAD = '''
from jax.experimental import pallas as pl

LANES = 128
ENTRY_SUBLANES = 32
ENTRY_BLOCK = ENTRY_SUBLANES * LANES
TILE_N = 4000                          # not lane-aligned

spec = pl.BlockSpec((ENTRY_SUBLANES, 100), lambda i: (i, 0))
'''

GEOMETRY_CLEAN = '''
from jax.experimental import pallas as pl

LANES = 128
ENTRY_SUBLANES = 32
ENTRY_BLOCK = ENTRY_SUBLANES * LANES
TILE_N = 4096

spec = pl.BlockSpec((ENTRY_SUBLANES, LANES), lambda i: (i, 0))
'''


def test_pallas_geometry_flags_misalignment():
    path = "src/repro/kernels/fixture.py"
    hits = assert_flags(PallasGeometryChecker, GEOMETRY_BAD,
                        GEOMETRY_CLEAN, paths=[path])
    msgs = " | ".join(f.message for f in hits)
    assert "TILE_N" in msgs and "minor dim 100" in msgs


def test_pallas_geometry_flags_vmem_blowout():
    blown = GEOMETRY_CLEAN.replace("TILE_N = 4096", "TILE_N = 131072")
    hits = run_checker(PallasGeometryChecker, [blown],
                       paths=["src/repro/kernels/fixture.py"])
    assert any("VMEM" in f.message for f in hits)


RING_CLEAN = '''
from jax.experimental import pallas as pl

LANES = 128
ENTRY_SUBLANES = 128
ENTRY_BLOCK = ENTRY_SUBLANES * LANES
CHUNK = 4096
RING_N_MAX = 8192

spec = pl.BlockSpec((ENTRY_SUBLANES, LANES), lambda i: (i, 0))
'''


def test_pallas_geometry_resident_ring_budget():
    # the fused plastic step's constants (RING_N_MAX resident ring):
    # clean at the shipped sizes, flagged when the ring and its per-row
    # one-hot outgrow the VMEM core
    path = "src/repro/kernels/fixture.py"
    assert not run_checker(PallasGeometryChecker, [RING_CLEAN],
                           paths=[path])
    blown = RING_CLEAN.replace("RING_N_MAX = 8192", "RING_N_MAX = 131072")
    hits = run_checker(PallasGeometryChecker, [blown], paths=[path])
    assert any("RING_N_MAX" in f.message for f in hits)


# ---------------------------------------------------------------------------
# deprecated-api
# ---------------------------------------------------------------------------

DEPRECATED_IMPORT_BAD = '''
from repro.core.engine import run, simulate
from repro.core import engine

def drive(s, t, c):
    run(s, t, c, 10)
    return engine.run_plastic(s, t, {}, c, 10)
'''

DEPRECATED_CLEAN = '''
from repro.core.engine import simulate

def analyze_run(d):
    return d

class Driver:
    def run(self, n):              # unrelated method named run
        return n

def drive(s, t, c, d):
    simulate(s, t, c, 10, plasticity={})
    analyze_run(d)
    return Driver().run(3)
'''


def test_deprecated_api_flags_imports_and_calls():
    hits = assert_flags(DeprecatedApiChecker, DEPRECATED_IMPORT_BAD,
                        DEPRECATED_CLEAN)
    msgs = "\n".join(f.message for f in hits)
    assert "import of retired" in msgs
    assert "run_plastic" in msgs and "simulate" in msgs


def test_deprecated_api_flags_alias_resurrection_in_engine():
    resurrected = ("def run(state, tables, cfg, n_steps):\n"
                   "    return state\n")
    hits = run_checker(DeprecatedApiChecker, [resurrected],
                       paths=["src/repro/core/engine.py"])
    assert hits and "redefinition" in hits[0].message
    # the same def anywhere else is NOT the retired alias
    assert not run_checker(DeprecatedApiChecker, [resurrected],
                           paths=["src/repro/runtime/other.py"])


# ---------------------------------------------------------------------------
# pragmas
# ---------------------------------------------------------------------------

def test_pragma_suppresses_inline_and_above_line():
    inline = ("import numpy as np\n"
              "x = np.random.rand(4)  "
              "# repro-lint: ignore[tracer-purity] fixture\n")
    above = ("import numpy as np\n"
             "# repro-lint: ignore[tracer-purity] fixture\n"
             "x = np.random.rand(4)\n")
    wrong_check = ("import numpy as np\n"
                   "x = np.random.rand(4)  "
                   "# repro-lint: ignore[donation] wrong pass\n")
    assert not run_checker(TracerPurityChecker, [inline])
    assert not run_checker(TracerPurityChecker, [above])
    assert run_checker(TracerPurityChecker, [wrong_check])


def test_file_pragma_suppresses_whole_file():
    src = ("# repro-lint: ignore-file[tracer-purity] generator fixture\n"
           "import numpy as np\n"
           "x = np.random.rand(4)\n"
           "y = np.random.randn(2)\n")
    assert not run_checker(TracerPurityChecker, [src])


# ---------------------------------------------------------------------------
# the live tree is clean, via the real CLI
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["text", "json"])
def test_analyzer_clean_on_live_src(fmt):
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.lint", "--format", fmt,
         SRC_ROOT],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_lists_all_six_passes():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.lint", "--list"],
        capture_output=True, text=True)
    assert proc.returncode == 0
    for cls in ALL_CHECKERS:
        assert cls.name in proc.stdout
    assert len(ALL_CHECKERS) >= 6


# ---------------------------------------------------------------------------
# AsyncWriterThread owning-thread assertion (the --sanitize runtime half)
# ---------------------------------------------------------------------------

class _Writer(AsyncWriterThread):
    """Minimal subclass with spooler-style non-queue state."""

    def __init__(self):
        self.offset = 0
        super().__init__()

    def _write(self, item):
        pass

    def append(self, n):
        self._assert_owner("append")
        self.offset += n
        self._submit(n)


@pytest.fixture
def thread_asserts():
    set_thread_asserts(True)
    try:
        yield
    finally:
        set_thread_asserts(False)


def test_owner_thread_append_passes_under_asserts(thread_asserts):
    w = _Writer()
    try:
        w.append(3)
        w.wait()
        assert w.offset == 3
    finally:
        w.close()


def test_foreign_thread_append_raises_under_asserts(thread_asserts):
    w = _Writer()
    err = []
    try:
        t = threading.Thread(
            target=lambda: err.append(
                pytest.raises(AssertionError, w.append, 1)))
        t.start()
        t.join()
        assert err and "owned by" in str(err[0].value)
        assert w.offset == 0           # the race never mutated state
    finally:
        w.close()


def test_asserts_off_by_default():
    assert not thread_asserts_enabled()
    w = _Writer()
    hit = []
    try:
        t = threading.Thread(target=lambda: hit.append(w.append(1)))
        t.start()
        t.join()
        assert w.offset == 1           # permissive without --sanitize
    finally:
        w.close()
