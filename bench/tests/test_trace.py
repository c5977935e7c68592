"""The trace reduction on a small trace recorded on a TPU v5e: the
3x3x100 test network, 45 steps in 5-step segments inside the
benchmark's window annotation."""

import pytest

from bench import trace

TRACE = str(__import__("pathlib").Path(__file__).parent
            / "data" / "small.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(TRACE))


def test_op_names():
    assert trace.op_name("%synaptic_accum.11 = f32[8,8,128] custom-call("
                         ") ...") == "synaptic_accum"
    assert trace.op_name("%copy-done.13 = pred[1] copy-done(...)") \
        == "copy-done"
    assert trace.op_name("%while.54 = (s32[]) while(...)") == "while"
    assert trace.op_name("fusion.3") == "fusion"


def test_self_times_take_nested_events_out():
    spans = [(0, 100, "while"), (10, 30, "fusion"), (40, 90, "k"),
             (50, 60, "fusion"), (200, 210, "copy")]
    got = trace._self_times(spans)
    assert got == pytest.approx({"while": 30e-9, "fusion": 30e-9,
                                 "k": 40e-9, "copy": 10e-9})


def test_reduction_of_a_recorded_window(reduced):
    r = reduced
    assert r["window_s"] == pytest.approx(0.110721682)
    assert 0 < r["busy_s"] < r["window_s"]
    # self times partition the busy time of the one device
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"])
    assert r["op_s"]["synaptic_accum"] > 0
    assert "while" in r["op_s"]
    ops = [v for _, v in r["device_ops"]]
    assert len(ops) == 10 and ops == sorted(ops, reverse=True)
    gaps = [g for _, g in r["idle_gaps"]]
    assert len(gaps) == 10 and gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= r["window_s"] - r["busy_s"] + 1e-12
    assert all(":" in name for name, _ in r["idle_gaps"])
    assert r["idle_gaps"][0][0] == "python3:$sim_driver.py:682 run"


def test_no_device_plane_reads_nothing(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = str(next(tmp_path.rglob("*.xplane.pb")))
    assert trace.reduce(trace.load(path)) is None
