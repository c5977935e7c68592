"""Runtime telemetry: span tracer semantics, JSONL/Chrome round-trips,
the spans' profiler annotations, and the pure-observer contract on the
segmented driver (spike trains and plastic weight checksums
bit-identical with tracing on or off, with or without a profiler
session, including across preempt -> resume)."""

import contextlib
import io
import json
import logging
import pathlib
import threading

import jax
import numpy as np
import pytest

from repro.core.connectivity import exponential_law, gaussian_law
from repro.core.dist_engine import DistConfig
from repro.core.engine import EngineConfig
from repro.core.grid import ColumnGrid, TileDecomposition
from repro.obs import telemetry as telemetry_mod
from repro.obs.telemetry import (FORMAT, Telemetry, enable_json_logging,
                                 read_jsonl, summarize)
from repro.parallel.compat import make_mesh
from repro.perf.trace import to_chrome_trace, write_chrome_trace
from repro.runtime import DriverConfig, SimDriver

N = 40

LAWS = {"gaussian": gaussian_law, "exponential": exponential_law}


def _dist_cfg(law="gaussian", stdp=None, seed=3):
    lw = LAWS[law]()
    dec = TileDecomposition(grid=ColumnGrid(4, 4, 10), tiles_y=1,
                            tiles_x=1, radius=lw.radius)
    return DistConfig(engine=EngineConfig(decomp=dec, law=lw, seed=seed,
                                          stdp=stdp))


def _driver(ckpt_dir, seg, law="gaussian", stdp=None, **kw):
    cfg = DriverConfig(ckpt_dir=str(ckpt_dir),
                       ckpt_every=kw.pop("ckpt_every", 1),
                       backoff_s=0.01, handle_sigterm=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    return SimDriver(cfg, _dist_cfg(law, stdp=stdp), mesh,
                     segment_steps=seg, **kw)


def _state_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@contextlib.contextmanager
def _profiler(trace_dir):
    """A CPU profiler session without the Python call tracer (the spans'
    annotations are recorded at any host tracer level)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _profile_spans(trace_dir, names):
    """{name: [(start_ns, duration_ns, stats)]} of the host events named
    in ``names`` in the session's ``.xplane.pb``, in start order."""
    path = next(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    prof = jax.profiler.ProfileData.from_file(str(path))
    out = {}
    for plane in prof.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns, dict(ev.stats)))
    return {k: sorted(v, key=lambda e: e[0]) for k, v in out.items()}


# ---------------------------------------------------------------------------
# tracer unit semantics
# ---------------------------------------------------------------------------

def test_span_nesting_and_thread_attribution():
    tel = Telemetry()
    with tel.span("outer", step=0):
        with tel.span("inner"):
            pass

    def worker():
        with tel.span("worker_span"):
            pass

    t = threading.Thread(target=worker, name="writer-0")
    t.start()
    t.join()

    outer, = tel.spans("outer")
    inner, = tel.spans("inner")
    wspan, = tel.spans("worker_span")
    # nesting: inner closed first, carries outer as parent, depth 1
    assert inner["parent"] == "outer" and inner["depth"] == 1
    assert outer["parent"] is None and outer["depth"] == 0
    assert outer["attrs"] == {"step": 0}
    assert outer["dur"] >= inner["dur"] >= 0
    # the worker thread has its own stack: no cross-thread parent, and
    # the record names the emitting thread
    assert wspan["parent"] is None and wspan["depth"] == 0
    assert wspan["thread"] == "writer-0"
    assert wspan["tid"] != outer["tid"]


def test_disabled_tracer_is_a_no_op_but_still_logs(caplog):
    tel = Telemetry(enabled=False)
    with tel.span("segment", step=0):
        pass
    tel.metrics("segment", wall_s=1.0)
    with caplog.at_level(logging.WARNING, logger="repro.telemetry"):
        tel.event("straggler", msg="step 3 overran", level="warning",
                  step=3)
    assert tel.records() == []            # nothing collected...
    assert "step 3 overran" in caplog.text   # ...but operators still see it
    assert caplog.records[0].repro_event == {"kind": "straggler",
                                             "step": 3}


def test_jsonl_roundtrip_and_chrome_schema(tmp_path):
    tel = Telemetry(jsonl_path=str(tmp_path / "t.jsonl"))
    with tel.span("segment", step=0):
        with tel.span("segment.compute", step=0):
            pass
    tel.event("straggler", msg="overran", level="warning", step=0,
              dt_s=2.0)
    tel.metrics("segment", step=0, wall_s=0.5, d_spikes=3.0)
    tel.flush_jsonl()

    back = read_jsonl(str(tmp_path / "t.jsonl"))
    assert [h["format"] for h in back["header"]] == [FORMAT]
    assert {s["name"] for s in back["span"]} == {"segment",
                                                 "segment.compute"}
    ev, = back["event"]
    assert ev["kind"] == "straggler" and ev["dt_s"] == 2.0
    m, = back["metrics"]
    assert m["kind"] == "segment" and m["d_spikes"] == 3.0

    trace = to_chrome_trace(tel.records(), pid=7)
    path = write_chrome_trace(tel, str(tmp_path / "trace.json"))
    with open(path) as f:
        on_disk = json.load(f)
    assert on_disk["otherData"]["format"] == FORMAT
    durs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in durs} == {"segment", "segment.compute"}
    for e in durs:
        assert e["pid"] == 7 and e["ts"] >= 0 and e["dur"] >= 0
    inner = next(e for e in durs if e["name"] == "segment.compute")
    assert inner["args"]["parent"] == "segment"
    instants = [e for e in trace["traceEvents"] if e["ph"] == "i"]
    assert {e["name"] for e in instants} == {"straggler", "segment"}
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert [e["args"]["name"] for e in meta] == ["MainThread"]


def test_flush_jsonl_is_exactly_once(tmp_path):
    path = str(tmp_path / "t.jsonl")
    tel = Telemetry(jsonl_path=path)
    with tel.span("a"):
        pass
    assert tel.flush_jsonl() == 1
    assert tel.flush_jsonl() == 0         # nothing new: no rewrite
    with tel.span("b"):
        pass
    assert tel.flush_jsonl() == 1         # only the new record appends
    back = read_jsonl(path)
    assert len(back["header"]) == 1
    assert [s["name"] for s in back["span"]] == ["a", "b"]


def test_summarize_aggregates_spans_segments_and_events(tmp_path):
    path = str(tmp_path / "t.jsonl")
    tel = Telemetry(jsonl_path=path)
    for step in (0, 10):
        with tel.span("segment", step=step):
            pass
        tel.metrics("segment", step=step, wall_s=0.5, steps_per_s=20.0,
                    d_spikes=3.0, d_events=7.0, d_dropped=0.0,
                    d_recorder_dropped=0.0)
    tel.event("straggler", msg="overran", level="warning", step=10)
    tel.flush_jsonl()

    s = summarize(read_jsonl(path))
    assert s["processes"] == 1
    seg_span = s["spans"]["segment"]
    assert seg_span["count"] == 2
    assert seg_span["total_s"] >= seg_span["max_s"] >= \
        seg_span["mean_s"] >= 0
    assert s["events"] == {"straggler": 1}
    seg = s["segments"]
    assert seg["n"] == 2 and seg["wall_s"] == 1.0
    assert seg["steps_per_s_mean"] == seg["steps_per_s_min"] == 20.0
    assert seg["d_spikes"] == 6.0 and seg["d_events"] == 14.0


def test_read_jsonl_refuses_foreign_streams(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text(json.dumps({"type": "header", "format": "other-v9"})
                 + "\n")
    with pytest.raises(ValueError, match="unknown telemetry format"):
        read_jsonl(str(p))
    p.write_text(json.dumps({"type": "span", "name": "x"}) + "\n")
    with pytest.raises(ValueError, match="no telemetry header"):
        read_jsonl(str(p))


def test_json_log_formatter_emits_structured_lines():
    stream = io.StringIO()
    handler = enable_json_logging(stream=stream)
    lg = logging.getLogger("repro")
    try:
        Telemetry(enabled=False).event(
            "preempt", msg="SIGTERM received", level="warning",
            logger=logging.getLogger("repro.runtime"), step=20)
    finally:
        lg.removeHandler(handler)
        lg.propagate = True
    rec = json.loads(stream.getvalue().strip())
    assert rec["level"] == "warning" and rec["msg"] == "SIGTERM received"
    assert rec["event"] == {"kind": "preempt", "step": 20}


# ---------------------------------------------------------------------------
# driver integration: pure observer + per-segment stream
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("law,profiled", [
    pytest.param("gaussian", False, id="gaussian"),
    pytest.param("exponential", False, id="exponential"),
    pytest.param("exponential", True, id="exponential-profiled"),
])
def test_pure_observer_bit_identity_static(tmp_path, law, profiled):
    """Tracing on vs off: bit-identical spike trains and final state,
    also with a profiler session recording the spans' annotations."""
    ref = _driver(tmp_path / "off", seg=10, law=law, record_events=True)
    out_ref = ref.run(N)
    tel = Telemetry()
    session = (_profiler(tmp_path / "prof") if profiled
               else contextlib.nullcontext())
    with session:
        traced = _driver(tmp_path / "on", seg=10, law=law,
                         record_events=True, telemetry=tel)
        out_tel = traced.run(N)
    np.testing.assert_array_equal(ref.spike_counts(N),
                                  traced.spike_counts(N))
    _state_equal(out_ref["state"], out_tel["state"])
    # and the tracer actually observed the run it did not perturb
    assert len(tel.spans("segment.compute")) == N // 10
    assert len([r for r in tel.records()
                if r["type"] == "metrics"]) == N // 10


def test_pure_observer_bit_identity_plastic_preempt_resume(tmp_path):
    """Traced preempt -> resume plastic run == untraced straight run,
    down to the tiling-invariant learned-weight checksum."""
    from repro.core.stdp import STDPParams
    ref = _driver(tmp_path / "ref", seg=10, stdp=STDPParams(),
                  record_events=True)
    out_ref = ref.run(N)

    first = _driver(tmp_path / "t", seg=10, stdp=STDPParams(),
                    record_events=True, preempt_after_segments=1,
                    telemetry=Telemetry())
    out1 = first.run(N)
    assert out1["preempted"] and out1["final_step"] == 10
    second = _driver(tmp_path / "t", seg=10, stdp=STDPParams(),
                     record_events=True, telemetry=Telemetry())
    out2 = second.run(N)
    assert out2["final_step"] == N

    np.testing.assert_array_equal(ref.spike_counts(N),
                                  second.spike_counts(N))
    _state_equal(out_ref["state"], out2["state"])
    assert ref.plastic_summary(out_ref["state"])["weight_checksum"] \
        == second.plastic_summary(out2["state"])["weight_checksum"]


def test_segment_stream_carries_deltas_and_spans(tmp_path):
    tel = Telemetry()
    drv = _driver(tmp_path, seg=10, record_events=True, telemetry=tel)
    out = drv.run(N)

    segs = [r for r in tel.records() if r["type"] == "metrics"
            and r["kind"] == "segment"]
    assert [m["step"] for m in segs] == [0, 10, 20, 30]
    for m in segs:
        assert m["wall_s"] > 0 and m["steps_per_s"] > 0
        for k in ("d_spikes", "d_events", "d_dropped",
                  "d_recorder_dropped"):
            assert k in m
    # deltas telescope back to the cumulative totals
    totals = drv.metric_totals(out["state"])
    assert sum(m["d_spikes"] for m in segs) == totals["spikes"]
    assert sum(m["d_events"] for m in segs) == totals["events"]
    # the same deltas ride the driver's metrics_log (--metrics-out)
    assert all("d_spikes" in row for row in out["metrics"])
    # every driver phase reported spans; writer-thread work is
    # attributed to the writer threads, not the main loop
    names = {s["name"] for s in tel.spans()}
    assert {"setup.tables", "setup.tables.sample", "setup.tables.compress",
            "setup.tables.place", "setup.sim_fn", "segment",
            "segment.compute", "segment.spool_drain", "segment.metrics",
            "ckpt.save", "ckpt.snapshot", "ckpt.spool_sync", "ckpt.d2h",
            "ckpt.write", "ckpt.drain", "spool.write", "restore.init"} <= names
    main_tid = tel.spans("segment")[0]["tid"]
    assert all(s["tid"] != main_tid for s in tel.spans("ckpt.write"))
    compute = tel.spans("segment.compute")[0]
    assert compute["parent"] == "segment" and compute["depth"] == 1
    # the between-segment host path: the counter read-back inside the
    # segment, the checkpoint hand-off after it, one of each a segment
    assert [s["attrs"]["step"] for s in tel.spans("segment.metrics")] \
        == [0, 10, 20, 30]
    assert all(s["parent"] == "segment"
               for s in tel.spans("segment.metrics"))
    # a checkpoint's spans carry its step: where its segment ended
    assert [s["attrs"]["step"] for s in tel.spans("ckpt.save")] \
        == [10, 20, 30, 40]
    assert all(s["parent"] == "ckpt.save"
               for s in tel.spans("ckpt.snapshot") + tel.spans(
                   "ckpt.spool_sync"))
    d2h = tel.spans("ckpt.d2h")
    assert [s["attrs"]["step"] for s in d2h] == [10, 20, 30, 40]
    state_bytes = sum(a.nbytes for a in jax.tree.leaves(out["state"]))
    assert all(s["attrs"]["bytes"] == state_bytes for s in d2h)


def test_analyze_cli_folds_in_telemetry_summary(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    drv = _driver(tmp_path, seg=10, record_events=True,
                  telemetry=Telemetry(jsonl_path=path))
    drv.run(N)
    drv.tel.flush_jsonl()

    from repro.launch.analyze import main as analyze_main
    out = analyze_main(["--run", f"r={tmp_path}",
                        "--telemetry", f"r={path}",
                        "--out", str(tmp_path / "report.json")])
    t = out["telemetry"]["r"]
    assert t["processes"] == 1
    assert t["segments"]["n"] == N // 10
    assert t["spans"]["segment.compute"]["count"] == N // 10
    with open(tmp_path / "report.json") as f:
        assert json.load(f)["telemetry"]["r"]["segments"]["n"] == N // 10


def test_exactly_once_stream_across_preempt_resume(tmp_path):
    """Each process appends its own header + records once; the stitched
    file holds every segment exactly once."""
    path = str(tmp_path / "telemetry.jsonl")
    tel1 = Telemetry(jsonl_path=path)
    d1 = _driver(tmp_path, seg=10, preempt_after_segments=1,
                 telemetry=tel1)
    d1.run(N)
    tel1.flush_jsonl()
    tel1.flush_jsonl()                    # idempotent final flush

    tel2 = Telemetry(jsonl_path=path)
    d2 = _driver(tmp_path, seg=10, telemetry=tel2)
    out = d2.run(N)
    assert out["final_step"] == N
    tel2.flush_jsonl()

    back = read_jsonl(path)
    assert len(back["header"]) == 2       # one per process
    segs = [m["step"] for m in back["metrics"]
            if m["kind"] == "segment"]
    assert sorted(segs) == [0, 10, 20, 30]
    resumes = [e for e in back["event"] if e["kind"] == "resume"]
    assert len(resumes) == 1 and resumes[0]["step"] == 10


# ---------------------------------------------------------------------------
# one timeline: spans in the profiler trace, set-up spans
# ---------------------------------------------------------------------------

def test_spans_land_in_the_profiler_trace(tmp_path):
    """Under a profiler session every span is also a host annotation
    with its attributes as stats, timed as the tracer timed it."""
    tel = Telemetry()
    with _profiler(tmp_path / "prof"):
        drv = _driver(tmp_path / "ckpt", seg=10, telemetry=tel)
        drv.run(20)
    names = {"setup.tables", "setup.tables.sample", "setup.tables.compress",
             "setup.tables.place", "segment.compute", "segment.metrics",
             "ckpt.save", "ckpt.d2h"}
    got = _profile_spans(tmp_path / "prof", names)
    assert set(got) == names
    for name in names:
        mine = sorted(tel.spans(name), key=lambda s: s["t0"])
        theirs = got[name]
        assert len(theirs) == len(mine), name
        for rec, (_, dur_ns, stats) in zip(mine, theirs):
            assert abs(dur_ns * 1e-9 - rec["dur"]) < 1e-3, name
            for k, v in rec.get("attrs", {}).items():
                # a list attribute becomes its string
                assert stats[k] == (str(v) if isinstance(v, list) else v)
    assert [s["step"] for _, _, s in got["segment.metrics"]] == [0, 10]
    assert [s["step"] for _, _, s in got["ckpt.d2h"]] == [10, 20]
    assert got["setup.tables.sample"][0][2]["tile"] == "[0, 0]"


def test_disabled_tracer_builds_no_annotation(tmp_path, monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("a profiler annotation was built")

    monkeypatch.setattr(telemetry_mod, "TraceAnnotation", refuse)
    drv = _driver(tmp_path, seg=10)              # the default NULL tracer
    assert drv.run(20)["final_step"] == 20
    Telemetry(enabled=False).annotate(synapses=1)
    # an enabled tracer does go through the patched name
    with pytest.raises(AssertionError, match="annotation was built"):
        with Telemetry().span("segment"):
            pass


@pytest.mark.parametrize("plastic", [False, True])
def test_setup_tables_spans_enclose_the_build(tmp_path, plastic):
    from repro.core.stdp import STDPParams
    tel = Telemetry()
    drv = _driver(tmp_path, seg=10, stdp=STDPParams() if plastic else None,
                  telemetry=tel)
    outer, = tel.spans("setup.tables")
    kids = [s for s in tel.spans() if s["name"].startswith("setup.tables.")]
    want = {"setup.tables.sample", "setup.tables.compress",
            "setup.tables.place"} | ({"setup.tables.inverse"}
                                     if plastic else set())
    assert {k["name"] for k in kids} == want
    for k in kids:
        assert k["parent"] == "setup.tables"
        assert k["depth"] == outer["depth"] + 1
        assert outer["t0"] <= k["t0"]
        assert k["t0"] + k["dur"] <= outer["t0"] + outer["dur"]
    assert sum(s["attrs"]["synapses"]
               for s in tel.spans("setup.tables.sample")) \
        == drv.table_stats["n_synapses"]
    place, = tel.spans("setup.tables.place")
    assert place["attrs"]["bytes"] == sum(
        a.nbytes for a in jax.tree.leaves(drv.tables))


@pytest.mark.parametrize("plastic", [False, True])
def test_sim_fn_span_names_the_delivery_weight_dtype(tmp_path, plastic):
    """Static tables (bfloat16 storage) deliver a bfloat16 weight stream
    in one MXU pass a lane row; plastic runs carry float32 weights and
    keep ``Precision.HIGHEST``.  The set-up span says which."""
    from repro.core.stdp import STDPParams
    tel = Telemetry()
    drv = _driver(tmp_path, seg=10, stdp=STDPParams() if plastic else None,
                  telemetry=tel)
    span, = tel.spans("setup.sim_fn")
    want = "float32" if plastic else "bfloat16"
    assert drv.storage.weight_dtype == want
    assert span["attrs"]["delivery_w_dtype"] == want


def test_table_sample_spans_one_per_shard():
    from repro.core.dist_engine import build_dist_tables
    lw = gaussian_law()
    dec = TileDecomposition(grid=ColumnGrid(4, 4, 10), tiles_y=2,
                            tiles_x=2, radius=lw.radius)
    cfg = DistConfig(engine=EngineConfig(decomp=dec, law=lw, seed=3))
    tel = Telemetry()
    _, stats = build_dist_tables(cfg, telemetry=tel)
    sample = tel.spans("setup.tables.sample")
    assert [s["attrs"]["tile"] for s in sample] \
        == [[0, 0], [0, 1], [1, 0], [1, 1]]
    assert all(s["attrs"]["synapses"] > 0 for s in sample)
    assert sum(s["attrs"]["synapses"] for s in sample) \
        == stats["n_synapses"]
    assert len(tel.spans("setup.tables.compress")) == 1


def test_sim_cli_trace_dir_profiles_set_up_and_spans(tmp_path,
                                                   monkeypatch):
    """``--trace-dir`` alone turns the span tracer on and opens the
    profile before the driver is built, so set-up spans land in it."""
    from repro.launch import sim
    # the CLI's persistent compile cache would outlive this test
    monkeypatch.setattr(sim, "enable_compile_cache", lambda: None)
    sim.main(["--grid", "4", "--neurons-per-column", "10", "--steps", "20",
              "--segment-steps", "10", "--ckpt-dir", str(tmp_path / "ckpt"),
              "--trace-dir", str(tmp_path / "prof")])
    got = _profile_spans(tmp_path / "prof", {"setup.tables.sample",
                                             "segment.compute", "ckpt.drain"})
    assert len(got["setup.tables.sample"]) == 1
    assert [s["step"] for _, _, s in got["segment.compute"]] == [0, 10]
    assert len(got["ckpt.drain"]) == 1
