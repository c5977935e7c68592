"""Device idle time put down to the program's spans (``bench/spans.py``)
and the per-layer metrics that read spans."""

import time

import pytest

from bench import run, spans, trace

TRACE = str(__import__("pathlib").Path(__file__).parent
            / "data" / "small.xplane.pb.gz")

# window [0, 100) ns; the device idles in [10, 20), [50, 60), [90, 100)
BUSY = [(0, 10), (20, 50), (60, 90)]
MAIN = [(5, 55, "segment"), (6, 18, "segment.compute"),
        (18, 25, "segment.metrics"), (55, 58, "ckpt.save"),
        (56, 57, "ckpt.snapshot")]


def test_innermost_span_labels_the_window():
    assert spans.innermost(MAIN, 0, 100) == [
        (0, 5, None), (5, 6, "segment"), (6, 18, "segment.compute"),
        (18, 25, "segment.metrics"), (25, 55, "segment"),
        (55, 56, "ckpt.save"), (56, 57, "ckpt.snapshot"),
        (57, 58, "ckpt.save"), (58, 100, None)]


def test_idle_goes_to_the_innermost_span():
    got = spans.attribute([BUSY], MAIN, 0, 100)
    assert got["by_span"] == pytest.approx({
        "segment.compute": 8e-9, "segment.metrics": 2e-9,
        "ckpt.save": 2e-9, "ckpt.snapshot": 1e-9})
    # the outer segment span's own time (50-55) explains nothing, nor
    # does time with no span open (58-60, 90-100)
    assert got["unattributed_s"] == pytest.approx(17e-9)
    assert got["idle_s"] == pytest.approx(30e-9)
    assert got["idle_s"] == pytest.approx(
        sum(got["by_span"].values()) + got["unattributed_s"])
    assert got["gaps"] == [["segment.compute", pytest.approx(1e-8)],
                           ["unattributed", pytest.approx(1e-8)],
                           ["unattributed", pytest.approx(1e-8)]]


def test_idle_is_averaged_over_devices():
    got = spans.attribute([BUSY, [(0, 100)]], MAIN, 0, 100)
    assert got["idle_s"] == pytest.approx(15e-9)
    assert got["by_span"]["segment.compute"] == pytest.approx(4e-9)


def test_recorded_v5e_window_without_program_spans():
    """The v5e trace predates the program's annotations: all of its idle
    time is unattributed, and it is the idle time ``bench/trace.py``
    reads."""
    profile = trace.load(TRACE)
    got = spans.idle_by_span(profile, {"segment", "segment.compute"})
    reduced = trace.reduce(profile)
    assert got["by_span"] == {}
    assert got["idle_s"] == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])
    assert got["unattributed_s"] == pytest.approx(got["idle_s"])
    assert [g for _, g in got["gaps"]] == pytest.approx(
        [g for _, g in reduced["idle_gaps"]])


def test_spans_read_back_from_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.obs.telemetry import Telemetry

    tel = Telemetry()
    with tel.span("setup.tables"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        window_t0 = time.perf_counter()
        for step in (0, 5):
            with tel.span("segment", step=step):
                with tel.span("segment.compute", step=step):
                    jnp.ones(8).sum().block_until_ready()
                with tel.span("segment.metrics", step=step):
                    time.sleep(0.002)
    jax.profiler.stop_trace()
    profile = trace.load(str(next(tmp_path.rglob("*.xplane.pb"))))
    names = {s["name"] for s in tel.spans()}
    got = spans.main_line_spans(profile, names)
    assert [n for _, _, n in sorted(got)] == [
        "segment", "segment.compute", "segment.metrics"] * 2
    mine = sorted(tel.spans(), key=lambda s: s["t0"])[1:]
    for (s, e, name), rec in zip(sorted(got), mine):
        assert name == rec["name"]
        assert abs((e - s) * 1e-9 - rec["dur"]) < 1e-3
    # no TPU plane: nothing to attribute
    assert spans.idle_by_span(profile, names) is None
    ctx = spans.context(tel, window_t0, profile)
    assert [s["name"] for s in ctx["setup_spans"]] == ["setup.tables"]
    assert ctx["span_idle"] is None


def span(name, dur):
    return {"name": name, "dur": dur}


def test_span_readers_read_nothing_without_their_spans():
    ctx = {"spans": [], "segments": 6}
    for name in ("table_sample_s", "table_place_s", "segment_sync_ms",
                 "idle_unattributed_share"):
        assert run.reader("layer", name)(ctx) is None, name
    ctx = {"spans": [span("ckpt.save", 1.0)], "segments": 6,
           "setup_spans": [span("setup.tables", 5.0)],
           "span_idle": {"idle_s": 0.0, "unattributed_s": 0.0}}
    for name in ("table_sample_s", "table_place_s", "segment_sync_ms",
                 "idle_unattributed_share"):
        assert run.reader("layer", name)(ctx) is None, name


def test_span_readers_on_a_synthetic_context():
    ctx = {
        "setup_spans": [span("setup.tables", 10.0),
                        span("setup.tables.sample", 4.0),
                        span("setup.tables.sample", 3.5),
                        span("setup.tables.compress", 1.0),
                        span("setup.tables.place", 0.5),
                        span("restore.init", 2.0)],
        "spans": [span("segment", 1.0), span("segment.metrics", 0.004),
                  span("ckpt.save", 0.002), span("ckpt.snapshot", 0.001),
                  span("segment.metrics", 0.006), span("ckpt.save", 0.008)],
        "segments": 2,
        "span_idle": {"idle_s": 0.1, "unattributed_s": 0.025,
                      "by_span": {"ckpt.drain": 0.075}},
    }
    read = {name: run.reader("layer", name)(ctx)
            for name in ("table_sample_s", "table_place_s",
                         "segment_sync_ms", "idle_unattributed_share")}
    assert read == pytest.approx({
        "table_sample_s": 7.5, "table_place_s": 1.5,
        "segment_sync_ms": 10.0, "idle_unattributed_share": 25.0})


def test_traced_run_gives_the_span_readers_their_context(small):
    """A traced run hands the set-up spans to the span readers; with no
    TPU plane in the trace there is no device idle time to attribute."""
    cell, config, traffic = small
    ctx = run.run_cell(cell, config, traffic, 2 ** 31 + 5, seconds=1.0,
                       trace=True, peaks={}, require_chip=False,
                       t_start=time.perf_counter())
    assert ctx["trace"] is None and ctx["span_idle"] is None
    names = {s["name"] for s in ctx["setup_spans"]}
    assert {"setup.tables", "setup.tables.sample",
            "setup.tables.place"} <= names
    assert run.reader("layer", "table_sample_s")(ctx) > 0
    assert run.reader("layer", "table_place_s")(ctx) > 0
    assert run.reader("layer", "idle_unattributed_share")(ctx) is None
