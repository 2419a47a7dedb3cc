"""The readers of the program's own spans: per-run span times and bytes
from hand-made run trees, the refresh programs' device time and the
idle time no layer explains from a hand-made trace and program spans,
and ``None`` wherever the program does not have what a reader reads."""
import glob
import os

import pytest

import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
from bench import program_spans
from bench.harness import OpRecord, RunRecord, reader
from bench.tracing import Event, TraceSummary, reduce_trace

RECORDED = os.path.join(bench_helpers.ROOT, "bench", "testdata")


def _span(name, secs, children=(), **attrs):
    out = {"name": name, "elapsed_s": secs}
    if attrs:
        out["attrs"] = attrs
    if children:
        out["children"] = list(children)
    return out


def _tree(plan, gathers, refresh_gather, wait, readback_bytes):
    return _span("run", 1.0, [
        _span("run.ingest", 0.001),
        _span("run.match", 0.3, [_span("store.match", 0.29, [
            _span("store.refresh", 0.1, [
                _span("store.refresh.gather", refresh_gather, rows=9)],
                h2d_bytes=4096),
            _span("store.match.combine", 0.05, [
                _span("store.match.wait", wait),
                _span("store.match.readback", 0.02,
                      d2h_bytes=readback_bytes)])])]),
        _span("run.plan", plan),
        _span("run.act", 0.5, [_span("run.act.gather", g, rows=4096)
                               for g in gathers])])


def _parent_tree():
    """A run tree of a program without this layer's spans."""
    return _span("run", 1.0, [
        _span("run.ingest", 0.001),
        _span("run.match", 0.3, [_span("store.match", 0.29, [
            _span("store.refresh", 0.1),
            _span("store.match.combine", 0.05)])]),
        _span("run.act", 0.5)])


def _record(trees, trace=None):
    ops = [OpRecord("policy_run", {}, 0.0, 0.0, 1.0, 0, spans=t)
           for t in trees]
    return RunRecord("scratch_purge.churn1", {}, {}, 1.0, 1.0, ops, trace,
                     None, [])


TREES = [_tree(0.04, [0.1, 0.2], 0.05, 0.01, 90_000_000),
         _tree(0.06, [0.3], 0.07, 0.03, 92_000_000)]


@pytest.mark.parametrize("metric,want", [
    ("plan_ms", 50.0),                  # (40 + 60) / 2
    ("act_gather_ms", 300.0),           # (100 + 200 + 300) / 2
    ("refresh_gather_ms", 60.0),
    ("match_wait_ms", 20.0),
    ("readback_mb", 91.0),
])
def test_span_readers_on_hand_made_trees(metric, want):
    read = reader(metric)
    assert read(_record(TREES)) == pytest.approx(want)
    assert read(_record([_parent_tree()])) is None
    assert read(_record([])) is None


def _summary(ops, host):
    return TraceSummary(ops, host, n_devices=1, window_s=1e-6)


def test_refresh_device_ms_reads_the_store_programs():
    read = reader("refresh_device_ms")
    ops = [Event(0, "%fusion = f32[8] fusion()", "jit_store_scatter_rows",
                 0, 3000),
           Event(0, "%pad = f32[8] pad()", "jit_store_pad_block", 5000, 1000),
           Event(0, "%k = f32[4] custom-call()", "jit_mesh_policy_scan_batch",
                 7000, 5000),
           # a scatter that starts before the run: only its part inside
           # counts; one after every run counts nothing
           Event(0, "%fusion = f32[8] fusion()", "jit_store_scatter_rows",
                 -500, 1000),
           Event(0, "%fusion = f32[8] fusion()", "jit_store_scatter_rows",
                 20000, 4000)]
    runs = [("bench.policy_run", 0, 6000), ("bench.churn", 6000, 2000),
            ("bench.policy_run", 8000, 6000)]
    rec = _record(TREES, _summary(ops, runs))
    assert read(rec) == pytest.approx((3000 + 1000 + 500) / 2 / 1e6)
    # a program whose store programs all run as jit_fn, and no trace
    unnamed = [Event(0, e.name, "jit_fn", e.start_ns, e.dur_ns)
               for e in ops[:2]]
    assert read(_record(TREES, _summary(unnamed, runs))) is None
    assert read(_record(TREES)) is None


def test_refresh_device_ms_is_absent_from_a_trace_before_the_names():
    files = glob.glob(os.path.join(RECORDED, "*.xplane.pb"))
    summary = reduce_trace(files[0], window_s=10.4)
    assert summary.matching(r"^jit_fn$")
    assert reader("refresh_device_ms")(_record(TREES, summary)) is None


# device 0 runs ops at [0,100], [400,500] and [900,1000]; the policy run
# is [0,800] and the churn after it; the program's spans nest as
# run [150,950] > run.act [200,500] > run.act.gather [250,350]
OPS = [Event(0, "%a", "jit_store_scatter_rows", 0, 100),
       Event(0, "%b", "jit_mesh_policy_scan_batch", 400, 100),
       Event(0, "%c", "jit_store_scatter_rows", 900, 100)]
HOST = [("bench.policy_run", 0, 800), ("bench.churn", 800, 200)]
SPANS = [("rbh.run", 150, 800), ("rbh.run.act", 200, 300),
         ("rbh.run.act.gather", 250, 100)]


def test_idle_time_is_put_down_to_the_innermost_span_at_each_instant():
    idle = program_spans.idle_by_span(_summary(OPS, HOST), SPANS)
    # gap [100,400]: none 50, run 50, act 50 + 50, gather 100; gap
    # [500,900] inside the run up to 800: run 300
    assert idle == pytest.approx({"none": 50e-9, "rbh.run": 350e-9,
                                  "rbh.run.act": 100e-9,
                                  "rbh.run.act.gather": 100e-9})
    segs = program_spans.segments(SPANS)
    assert [s[2] for s in segs] == ["rbh.run", "rbh.run.act",
                                    "rbh.run.act.gather", "rbh.run.act",
                                    "rbh.run"]


def test_idle_unspanned_ms_reads_run_and_none(monkeypatch):
    read = reader("idle_unspanned_ms")
    rec = _record(TREES, _summary(OPS, HOST))
    monkeypatch.setattr(program_spans, "host_spans", lambda r: SPANS)
    # (none 50 + run 350) ns over the record's 2 policy runs
    assert read(rec) == pytest.approx(400e-9 / 2 * 1e3)
    # a program without the annotations, and an untraced run
    monkeypatch.setattr(program_spans, "host_spans", lambda r: [])
    assert read(rec) is None
    assert read(_record(TREES)) is None


def test_spans_are_read_back_from_a_recorded_trace(tmp_path):
    import jax
    from repro.core import MetricRegistry
    reg = MetricRegistry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with reg.trace("run"):
            with reg.trace("run.plan"):
                pass
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                    "*", "*.xplane.pb"))
    spans = sorted(program_spans.read_spans(path), key=lambda s: s[1])
    assert [s[0] for s in spans] == ["rbh.run", "rbh.run.plan"]
    assert [s[2] for s in program_spans.segments(spans)][:2] == [
        "rbh.run", "rbh.run.plan"]
