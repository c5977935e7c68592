"""Multi-device behaviour, each case in a subprocess (XLA device count
is locked at first jax init, so the main pytest process must stay
single-device)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every case spawns an 8-device subprocess simulation; minutes on CPU
pytestmark = pytest.mark.slow


def run_py(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.compat import make_mesh, shard_map
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
"""


def test_halo_exchange_exact():
    run_py(PRELUDE + """
from repro.core.halo import exchange_halo_2d, pack_bits, unpack_bits
TY, TX, th, tw, F, R = 4, 2, 3, 3, 5, 4
gh, gw = TY*th, TX*tw
rng = np.random.default_rng(0)
glob = rng.integers(0, 2, size=(gh, gw, F)).astype(np.float32)
tiles = glob.reshape(TY, th, TX, tw, F).transpose(0, 2, 1, 3, 4)
def body(x):
    x = x[0, 0]
    reg = exchange_halo_2d(x, radius=R, axis_y=("pod", "data"),
                           axis_x="model", mode="strip")
    regp = unpack_bits(exchange_halo_2d(pack_bits(x), radius=R,
        axis_y=("pod", "data"), axis_x="model"), F)
    return reg[None, None], regp[None, None]
sm = jax.jit(shard_map(body, mesh=mesh,
    in_specs=(P(("pod", "data"), "model"),),
    out_specs=(P(("pod", "data"), "model"),)*2))
reg, regp = sm(jnp.asarray(tiles))
pad = np.pad(glob, ((R, R), (R, R), (0, 0)))
for ty in range(TY):
    for tx in range(TX):
        want = pad[ty*th:ty*th+th+2*R, tx*tw:tx*tw+tw+2*R]
        assert np.array_equal(want, np.asarray(reg)[ty, tx]), (ty, tx)
assert np.array_equal(np.asarray(regp), np.asarray(reg))
print("halo OK")
""")


def test_distributed_snn_simulation():
    run_py(PRELUDE + """
from repro.core.connectivity import exponential_law
from repro.core.grid import ColumnGrid, TileDecomposition
from repro.core.engine import EngineConfig
from repro.core.dist_engine import DistConfig, simulate
law = exponential_law()
dec = TileDecomposition(grid=ColumnGrid(8, 8, 40), tiles_y=4, tiles_x=2,
                        radius=law.radius)
cfg = DistConfig(engine=EngineConfig(decomp=dec, law=law),
                 axis_y=("pod", "data"), axis_x="model")
out = simulate(cfg, mesh, n_steps=40)
assert out["dropped"] == 0
assert np.isfinite(out["rate_hz"]) and out["rate_hz"] >= 0
assert out["events"] >= 0
print("dist sim OK", out["rate_hz"])
""")


def test_dist_matches_single_shard_statistics():
    """Same global model, 1-shard vs 8-shard: firing-rate statistics
    agree (different RNG streams -> statistical, not bitwise)."""
    run_py(PRELUDE + """
from repro.core.connectivity import gaussian_law
from repro.core.grid import ColumnGrid, TileDecomposition
from repro.core.engine import (EngineConfig, build_shard_tables,
                               init_sim_state, firing_rate_hz,
                               simulate as engine_simulate)
from repro.core.dist_engine import DistConfig, simulate
law = gaussian_law()
grid = ColumnGrid(8, 8, 40)
# single shard
d1 = TileDecomposition(grid=grid, tiles_y=1, tiles_x=1, radius=law.radius)
c1 = EngineConfig(decomp=d1, law=law, seed=5)
t1 = build_shard_tables(c1)
s1, _ = jax.jit(lambda s: engine_simulate(s, t1, c1, 400))(init_sim_state(c1))
r1 = firing_rate_hz(s1, c1, 400)
# 8 shards
d8 = TileDecomposition(grid=grid, tiles_y=4, tiles_x=2, radius=law.radius)
c8 = DistConfig(engine=EngineConfig(decomp=d8, law=law, seed=5),
                axis_y=("pod", "data"), axis_x="model")
out = simulate(c8, mesh, n_steps=400)
r8 = out["rate_hz"]
print("rates:", r1, r8)
assert r8 == __import__("pytest").approx(r1, rel=0.35)
""")


def test_sim_driver_retile_resume(tmp_path):
    """Checkpoint a 1x2-tiled segmented run, resume it 2x1 with elastic
    re-tiling: the relayout is exact per global column id (spot-checked
    against the checkpoint) and the resumed run proceeds sanely."""
    run_py(f"""
import jax, numpy as np
from repro.checkpoint.store import latest_step, restore_checkpoint
from repro.core.connectivity import gaussian_law
from repro.core.dist_engine import DistConfig, abstract_dist_inputs
from repro.core.engine import EngineConfig, firing_rate_hz
from repro.core.grid import ColumnGrid, TileDecomposition
from repro.core.retile import neuron_gather_map
from repro.parallel.compat import make_mesh
from repro.runtime import DriverConfig, SimDriver

def dist(ty, tx):
    law = gaussian_law()
    dec = TileDecomposition(grid=ColumnGrid(6, 6, 20), tiles_y=ty,
                            tiles_x=tx, radius=law.radius)
    return DistConfig(engine=EngineConfig(decomp=dec, law=law, seed=4))

ck = {str(tmp_path)!r}
m12 = make_mesh((1, 2), ("data", "model"))
d1 = SimDriver(DriverConfig(ckpt_dir=ck, ckpt_every=1,
                            handle_sigterm=False),
               dist(1, 2), m12, segment_steps=30)
out1 = d1.run(60)
assert out1["final_step"] == 60

m21 = make_mesh((2, 1), ("data", "model"))
d2 = SimDriver(DriverConfig(ckpt_dir=ck, ckpt_every=1,
                            handle_sigterm=False),
               dist(2, 1), m21, segment_steps=30, allow_retile=True)
start, state = d2._restore_or_init()
assert start == 60
# exact relayout: compare against the raw checkpoint per global col id
old = restore_checkpoint(ck, 60, abstract_dist_inputs(dist(1, 2))[0])
src = neuron_gather_map(dist(1, 2).engine.decomp, dist(2, 1).engine.decomp)
for k in ("v", "c", "refrac"):
    got = np.asarray(state["neuron"][k])
    want = np.asarray(old["neuron"][k]).reshape(-1)[src]
    np.testing.assert_array_equal(got[src >= 0], want[src >= 0], err_msg=k)
ring_old = np.moveaxis(np.asarray(old["i_ring"]), 2, 0)
ring_new = np.moveaxis(np.asarray(state["i_ring"]), 2, 0)
for s in range(ring_old.shape[0]):
    np.testing.assert_array_equal(ring_new[s][src >= 0],
                                  ring_old[s].reshape(-1)[src][src >= 0])
assert int(np.max(np.asarray(state["t"]))) == 60
out2 = d2.run(120)
assert out2["final_step"] == 120
# driver-level rate: re-adds the manifest-carried metric base the
# retile moved out of the per-tile state (engine.firing_rate_hz on a
# retiled state would silently undercount the pre-retile half)
rate = d2.firing_rate_hz(out2["state"])
state_rate = firing_rate_hz(out2["state"], dist(2, 1).engine)
assert np.isfinite(rate) and 0.0 <= rate < 200.0
assert state_rate <= rate  # state alone lost the pre-retile history
print("retile resume OK", rate)
""", devices=2)


def test_sim_driver_plastic_retile_resume(tmp_path):
    """A plastic run born on 1x2 resumes on 2x1: the learned weight
    tables are relaid by global (pre, post) synapse id -- bit-identical
    per synapse (checksum) -- and the run keeps learning on the new
    tiling.  The relay is timed as its own child of ``setup.tables``."""
    run_py(f"""
import numpy as np
from repro.core.connectivity import gaussian_law
from repro.core.dist_engine import DistConfig
from repro.core.engine import EngineConfig
from repro.core.grid import ColumnGrid, TileDecomposition
from repro.core.stdp import STDPParams
from repro.obs.telemetry import Telemetry
from repro.parallel.compat import make_mesh
from repro.runtime import DriverConfig, SimDriver

def dist(ty, tx):
    law = gaussian_law()
    dec = TileDecomposition(grid=ColumnGrid(6, 6, 20), tiles_y=ty,
                            tiles_x=tx, radius=law.radius)
    return DistConfig(engine=EngineConfig(decomp=dec, law=law, seed=4,
                                          stdp=STDPParams()))

ck = {str(tmp_path)!r}
m12 = make_mesh((1, 2), ("data", "model"))
d1 = SimDriver(DriverConfig(ckpt_dir=ck, ckpt_every=1,
                            handle_sigterm=False),
               dist(1, 2), m12, segment_steps=30)
out1 = d1.run(60)
assert out1["final_step"] == 60
s1 = d1.plastic_summary(out1["state"])
assert s1["w_l1_delta"] > 0 and s1["n_plastic"] > 0  # learning happened

m21 = make_mesh((2, 1), ("data", "model"))
tel = Telemetry()
d2 = SimDriver(DriverConfig(ckpt_dir=ck, ckpt_every=1,
                            handle_sigterm=False),
               dist(2, 1), m21, segment_steps=30, allow_retile=True,
               telemetry=tel)
assert d2._born_tiles == (1, 2)        # birth tiling from checkpoint meta
kids = {{s["name"] for s in tel.spans() if s["parent"] == "setup.tables"}}
assert kids == {{"setup.tables.sample", "setup.tables.compress",
                "setup.tables.retile", "setup.tables.place",
                "setup.tables.inverse"}}, kids
start, state = d2._restore_or_init()
assert start == 60
s2 = d2.plastic_summary(state)
# the relay preserved every learned weight bit-exactly per synapse id
assert s2["weight_checksum"] == s1["weight_checksum"], (s1, s2)
out2 = d2.run(120)
assert out2["final_step"] == 120
s3 = d2.plastic_summary(out2["state"])
assert s3["w_l1_delta"] >= s2["w_l1_delta"]
rate = d2.firing_rate_hz(out2["state"])
assert np.isfinite(rate) and 0.0 <= rate < 200.0
print("plastic retile OK", rate)
""", devices=2)


def test_moe_ep_equals_dense():
    run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.compat import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
from repro.models import ModelConfig
from repro.models.moe import init_moe, _apply_moe_dense, _apply_moe_ep
from repro.parallel.sharding import MeshRules, rules_for_mesh
rules = rules_for_mesh(mesh)
nomesh = MeshRules(batch=None, fsdp=None, heads=None, mlp=None,
                   experts=None, vocab=None, kv_seq=None, d_inner=None)
cfg = ModelConfig(name="moe", family="moe", n_layers=1, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=16, vocab_size=128,
                  n_experts=8, moe_top_k=2, capacity_factor=8.0,
                  dtype="float32")
p, _ = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
y_ref, _ = _apply_moe_dense(p, cfg, nomesh, x)
y_ep, _ = jax.jit(lambda p, x: _apply_moe_ep(p, cfg, rules, x))(p, x)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                           rtol=2e-5, atol=2e-5)
g = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(
    _apply_moe_ep(p, cfg, rules, x)[0]))))(p, x)
gr = jax.grad(lambda p, x: jnp.sum(jnp.sin(
    _apply_moe_dense(p, cfg, nomesh, x)[0])))(p, x)
for k in g:
    np.testing.assert_allclose(np.asarray(g[k]), np.asarray(gr[k]),
                               rtol=5e-4, atol=5e-4, err_msg=k)
print("EP OK")
""")


def test_sharded_train_step_matches_single_device():
    """The same train step, 1 device vs 4x2 mesh: identical loss."""
    run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.compat import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
from repro.models import ModelConfig
from repro.models.transformer import init_model
from repro.models.model import loss_fn
from repro.parallel.sharding import MeshRules, rules_for_mesh
cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=256,
                  dtype="float32", attn_chunk_q=32, attn_chunk_k=32,
                  loss_chunk=32)
params, specs = init_model(jax.random.PRNGKey(0), cfg)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, 256)
batch = {"tokens": tokens, "labels": tokens}
nomesh = MeshRules(batch=None, fsdp=None, heads=None, mlp=None,
                   experts=None, vocab=None, kv_seq=None, d_inner=None)
l_single, _ = loss_fn(params, cfg, nomesh, batch)
rules = rules_for_mesh(mesh)
psh = rules.shardings(specs, mesh)
params_sh = jax.device_put(params, psh)
l_mesh, _ = jax.jit(lambda p, b: loss_fn(p, cfg, rules, b))(params_sh, batch)
np.testing.assert_allclose(float(l_single), float(l_mesh), rtol=1e-5)
print("sharded loss OK", float(l_single))
""")


def test_elastic_restore_onto_different_mesh(tmp_path):
    """Checkpoint on a (4,2) mesh, restore onto (2,4): values identical."""
    run_py(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.store import save_checkpoint, restore_checkpoint
from repro.parallel.compat import make_mesh
m1 = make_mesh((4, 2), ("data", "model"))
m2 = make_mesh((2, 4), ("data", "model"))
x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
x1 = jax.device_put(x, NamedSharding(m1, P("data", "model")))
save_checkpoint({str(tmp_path)!r}, 3, {{"w": x1}})
out = restore_checkpoint({str(tmp_path)!r}, 3, {{"w": x}},
    shardings={{"w": NamedSharding(m2, P("data", "model"))}})
np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(x))
assert len(out["w"].sharding.device_set) == 8
print("elastic OK")
""")


def test_compressed_pod_gradient_sync():
    """int8+error-feedback cross-pod DP: first step matches the exact
    step to int8 precision and training still converges."""
    run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.compat import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
from repro.models import ModelConfig
from repro.models.transformer import init_model
from repro.models.model import make_train_step, make_compressed_pod_train_step
from repro.optim import adamw
from repro.optim.compression import init_residuals
from repro.optim.schedules import constant
from repro.parallel.sharding import rules_for_mesh
rules = rules_for_mesh(mesh)
cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=256,
                  dtype="float32", attn_chunk_q=32, attn_chunk_k=32,
                  loss_chunk=32)
params, _ = init_model(jax.random.PRNGKey(0), cfg)
opt = adamw(constant(1e-3))
opt_state = opt.init(params)
tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, 256)
batch = {"tokens": tokens, "labels": tokens}
p1, o1, out1 = jax.jit(make_train_step(cfg, rules, opt))(params, opt_state, batch)
resid = init_residuals(params)
step_c = jax.jit(make_compressed_pod_train_step(cfg, rules, opt))
p2, o2, resid, out2 = step_c(params, opt_state, resid, batch)
assert abs(float(out1["loss"]) - float(out2["loss"])) < 1e-5
d = max(float(jnp.abs(a - b).max())
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
assert d < 5e-3, d
for _ in range(5):
    p2, o2, resid, out2 = step_c(p2, o2, resid, batch)
assert float(out2["loss"]) < float(out1["loss"])
print("compressed pod sync OK")
""")
