"""Unified telemetry plane: registry, spans, export, fallback telemetry.

Covers the cross-cutting contracts the per-component suites don't:
the registry backing every pre-existing counter, the run span tree,
Prometheus round-trip, the scrape-boundary reset clearing all counter
families together, and each documented evaluator downgrade recorded as a
``fallback{stage=,reason=}`` counter matching ``RunReport`` /
``Reports`` string telemetry.
"""
import threading

import pytest

from repro.core import (AlertManager, AlertRule, Catalog, EventPipeline,
                        MetricRegistry, PipelineConfig, PolicyDefinition,
                        PolicyEngine, Reports, Scanner, StatsAggregator,
                        parse_prometheus)
from repro.core.telemetry import slug, span
from repro.fs import LustreSim


def _fs(n_files: int = 30):
    fs = LustreSim(n_osts=4)
    proj = fs.mkdir(fs.root_fid(), "proj")
    for i in range(n_files):
        f = fs.create(proj, f"data{i}.bin", owner=f"u{i % 3}")
        fs.write(f, (i + 1) * 100)
    return fs, proj


# -- registry ------------------------------------------------------------------
def test_counter_gauge_histogram_families():
    reg = MetricRegistry()
    reg.counter("events", kind="a").inc(3)
    reg.counter("events", kind="b").inc()
    reg.gauge("depth").set(7.5)
    h = reg.histogram("lat", edges=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["events"]["series"]["kind=a"] == 3
    assert snap["events"]["series"]["kind=b"] == 1
    assert snap["depth"]["series"][""] == 7.5
    hs = snap["lat"]["series"][""]
    assert hs["count"] == 4 and hs["counts"] == [1, 2, 1, 0]
    assert 0.01 <= hs["p50"] <= 0.1


def test_histogram_memory_is_bounded_and_percentile_sane():
    reg = MetricRegistry()
    h = reg.histogram("h", edges=(1.0, 2.0, 4.0))
    for i in range(10_000):
        h.observe(float(i % 5))
    assert len(h.counts) == 4        # fixed buckets, not 10k samples
    assert 1.0 <= h.percentile(0.5) <= 4.0


def test_same_name_different_kind_rejected():
    reg = MetricRegistry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")


def test_disabled_registry_is_noop_but_readable():
    reg = MetricRegistry(enabled=False)
    reg.counter("c").inc(5)
    reg.histogram("h").observe(1.0)
    with reg.trace("t"):
        pass
    assert reg.counter("c").value == 0
    assert reg.histogram("h").count == 0
    assert reg.spans() == []


def test_prometheus_roundtrip_and_escaping():
    reg = MetricRegistry()
    reg.counter("ops", help="ops done", stage='we"ird\nname').inc(2)
    reg.gauge("depth", mdt="0").set(3)
    reg.histogram("lat", edges=(0.1, 1.0)).observe(0.5)
    reg.state("why").set("policy_scan->numpy: glob")
    text = reg.render_prometheus()
    parsed = parse_prometheus(text)          # raises on malformed lines
    assert any(k.startswith("ops") for k in parsed)
    assert parsed['lat_bucket{le="+Inf"}'] == 1
    assert parsed['lat_count'] == 1
    with pytest.raises(ValueError):
        parse_prometheus("not a metric line at all }{")


def test_callback_gauges_read_live_state():
    reg = MetricRegistry()
    depth = {"v": 1}
    reg.register_callback("queue_depth",
                          lambda: [({"q": "main"}, depth["v"])])
    assert reg.snapshot()["queue_depth"]["series"]["q=main"] == 1
    depth["v"] = 9
    assert reg.snapshot()["queue_depth"]["series"]["q=main"] == 9
    assert parse_prometheus(reg.render_prometheus())[
        'queue_depth{q="main"}'] == 9


def test_trace_nesting_and_threads():
    reg = MetricRegistry()
    with reg.trace("outer") as sp:
        with reg.trace("inner"):
            pass
        sp.annotate(tag=1)

    def worker():
        with reg.trace("thread_root"):
            pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    roots = reg.spans()
    names = [s.name for s in roots]
    assert "outer" in names and "thread_root" in names
    outer = reg.spans("outer")[0]
    assert [c.name for c in outer.children] == ["inner"]
    assert outer.elapsed >= outer.children[0].elapsed
    # every close also feeds span_seconds{span=}
    assert reg.snapshot()["span_seconds"]["series"]["span=inner"]["count"] == 1


def test_ambient_span_is_noop_outside_trace():
    with span("orphan") as sp:          # no active trace: shared no-op
        sp.annotate(ignored=True)
    reg = MetricRegistry()
    with reg.trace("root"):
        with span("child", idx=1):
            pass
    assert [c.name for c in reg.spans("root")[0].children] == ["child"]


def test_slug_bounds_label_cardinality():
    s = slug("policy_scan_mesh->policy_scan: no device store " * 20)
    assert len(s) <= 60 and s == slug(s)  # idempotent, bounded, sanitized


# -- component wiring ----------------------------------------------------------
def test_one_registry_backs_all_component_counters():
    fs, _ = _fs()
    cat = Catalog()
    stats = StatsAggregator(cat.strings)
    cat.add_delta_hook(stats.on_delta)
    Scanner(fs, cat).scan()
    rep = Reports(cat, stats)
    cat.arrays()
    rep.du("/proj")
    rep.find("size > 1000")
    values = cat.telemetry.counter_values()
    assert values['catalog_arrays_calls{catalog="catalog0"}'] \
        == cat.arrays_calls
    assert values['reports_host_served{reports="reports0"}'] \
        == rep.host_served == 2
    assert values['reports_index_rebuilds{reports="reports0"}'] \
        == rep.index_rebuilds


def test_injected_shared_registry_instance_labels():
    reg = MetricRegistry()
    a, b = Catalog(telemetry=reg), Catalog(telemetry=reg)
    a.arrays()
    a.arrays()
    b.arrays()
    assert a.arrays_calls == 2 and b.arrays_calls == 1
    vals = reg.counter_values()
    assert vals['catalog_arrays_calls{catalog="catalog0"}'] == 2
    assert vals['catalog_arrays_calls{catalog="catalog1"}'] == 1


def test_pipeline_and_stream_telemetry():
    fs = LustreSim(n_mdts=1)
    d = fs.mkdir(fs.root_fid(), "dir")
    cat = Catalog()
    stream = fs.changelog.stream(0)
    pipe = EventPipeline(fs, cat, stream, PipelineConfig())
    assert stream.telemetry is cat.telemetry
    for i in range(10):
        f = fs.create(d, f"f{i}", owner="u", uid="u")
        fs.write(f, 100)
    assert stream.backlog() > 0
    pipe.process_once(100000)
    assert stream.backlog() == 0
    assert stream.lag_seconds() == 0.0
    vals = cat.telemetry.counter_values()
    assert vals['changelog_events_emitted{mdt="0"}'] >= 20   # 10x(create+write)
    assert vals['pipeline_records_processed{pipeline="pipeline0"}'] \
        == pipe.processed > 0
    snap = cat.telemetry.snapshot()
    series = snap["changelog_backlog_mdt0"]["series"]
    assert series and all(v == 0 for v in series.values())


# -- scrape-boundary reset (satellite: reset clears ALL families) --------------
def test_reset_counters_clears_every_family_together():
    fs, _ = _fs()
    cat = Catalog()
    Scanner(fs, cat).scan()
    rep = Reports(cat)
    rep.du("/proj")
    rep.find("path == '/proj/*.bin'")      # glob: host fold
    assert rep.host_served == 2 and rep.index_rebuilds > 0
    assert cat.arrays_calls > 0
    # a fallback leaves both the string state and the counter family
    rep.last_fallback_reason = "find: synthetic"
    vals = cat.telemetry.counter_values()
    assert any(v for v in vals.values())
    rep.reset_counters()
    assert (rep.store_served, rep.host_served, rep.index_rebuilds) \
        == (0, 0, 0)
    assert rep.last_fallback_reason is None
    assert cat.arrays_calls == 0           # same registry, same boundary
    assert all(v == 0 for v in cat.telemetry.counter_values().values())
    hists = [f for f in cat.telemetry.snapshot().values()
             if f["kind"] == "histogram"]
    assert all(s["count"] == 0 for f in hists for s in f["series"].values())


# -- fallback chain as telemetry (satellite: no silent downgrades) -------------
def _engine(fs, cat, evaluator):
    Scanner(fs, cat).scan()
    eng = PolicyEngine(cat, clock=lambda: 2e9)
    hits = []
    pd = PolicyDefinition.from_config(
        "p", lambda e, params: hits.append(e) or True,
        scope="path == '/proj/*.bin'",   # glob: kernel paths must degrade
        evaluator=evaluator, mutates=False, dry_run=True)
    eng.register(pd)
    return eng


def _fallback_series(reg):
    out = {}
    for name, value in reg.counter_values().items():
        if name.startswith("fallback{"):
            out[name] = value
    return out


def test_fallback_chain_mesh_to_policy_scan_to_numpy():
    fs, _ = _fs()
    cat = Catalog()
    # no device store attached: policy_scan_mesh must degrade to
    # policy_scan, whose glob predicate then degrades to numpy — BOTH
    # edges must land in the registry and match the RunReport string
    eng = _engine(fs, cat, "policy_scan_mesh")
    rep = eng.run("p", matching="full")
    assert rep.evaluator == "numpy"
    assert "policy_scan_mesh->policy_scan" in rep.fallback_reason
    assert "policy_scan->numpy" in rep.fallback_reason
    series = _fallback_series(cat.telemetry)
    stages = [k for k in series]
    assert any('stage="policy_scan_mesh->policy_scan"' in k
               for k in stages), stages
    assert any('stage="policy_scan->numpy"' in k for k in stages), stages
    assert sum(series.values()) == 2
    # the same deltas ride on the run's own telemetry
    run_counters = rep.telemetry["counters"]
    assert sum(v for k, v in run_counters.items()
               if k.startswith("fallback{")) == 2


def test_fallback_policy_scan_to_numpy_only():
    fs, _ = _fs()
    cat = Catalog()
    eng = _engine(fs, cat, "policy_scan")
    rep = eng.run("p", matching="full")
    assert rep.evaluator == "numpy"
    assert rep.fallback_reason.startswith("policy_scan->numpy")
    series = _fallback_series(cat.telemetry)
    assert len(series) == 1 and sum(series.values()) == 1
    assert 'stage="policy_scan->numpy"' in next(iter(series))


def test_no_fallback_records_nothing():
    fs, _ = _fs()
    cat = Catalog()
    eng = _engine(fs, cat, "numpy")
    rep = eng.run("p", matching="full")
    assert rep.fallback_reason == ""
    assert _fallback_series(cat.telemetry) == {}


def test_reports_fallback_counter_matches_string():
    fs, _ = _fs()
    cat = Catalog()
    Scanner(fs, cat).scan()
    rep = Reports(cat)
    rep.find("path == '/proj/*.bin'")
    # no store attached: host path, no fallback counter (nothing degraded)
    assert _fallback_series(cat.telemetry) == {}
    assert rep.last_fallback_reason is None


# -- run span tree -------------------------------------------------------------
def test_run_report_carries_span_tree_and_counter_deltas():
    fs, _ = _fs()
    cat = Catalog()
    eng = _engine(fs, cat, "numpy")
    rep = eng.run("p", matching="full")
    tree = rep.telemetry["spans"]
    assert tree["name"] == "run"
    child_names = [c["name"] for c in tree["children"]]
    assert child_names[:2] == ["run.ingest", "run.match"]
    assert "run.act" in child_names
    assert tree["elapsed_s"] >= 0
    # deltas only contain series this run actually moved
    assert all(v != 0 for v in rep.telemetry["counters"].values())
    # disabled registry: no per-run telemetry, run still works
    cat.telemetry.enabled = False
    rep2 = eng.run("p", matching="full")
    assert rep2.telemetry == {}


def test_worker_thread_spans_join_the_run_tree():
    """With several action threads, each chunk's catalog read lands under
    ``run.act``, once per chunk, and none becomes an orphan root span."""
    fs, _ = _fs(30)
    cat = Catalog()
    Scanner(fs, cat).scan()
    eng = PolicyEngine(cat, clock=lambda: 2e9)
    eng.register(PolicyDefinition.from_config(
        "p", lambda e, params: True, scope="type == file",
        evaluator="numpy", mutates=False, n_threads=4, batch_size=4))
    rep = eng.run("p", matching="full")
    assert rep.matched == 30
    tree = rep.telemetry["spans"]
    [act] = [c for c in tree["children"] if c["name"] == "run.act"]
    gathers = [c for c in act["children"] if c["name"] == "run.act.gather"]
    assert len(gathers) == -(-rep.matched // 4)
    assert sum(g["attrs"]["rows"] for g in gathers) == rep.matched
    assert cat.telemetry.spans("run.act.gather") == []


def _rows_series(engine, policy, stage):
    return (f'policy_rows{{engine="{engine}",policy="{policy}",'
            f'stage="{stage}"}}')


def test_policy_rows_count_each_policy_apart():
    """``policy_rows{policy,stage}`` adds every run's matched rows and its
    actioned (succeeded) rows under the run's own policy."""
    fs, _ = _fs(30)
    cat = Catalog()
    Scanner(fs, cat).scan()
    eng = PolicyEngine(cat, clock=lambda: 2e9)
    eng.register(PolicyDefinition.from_config(
        "big", lambda e, params: e.size % 200 == 0,   # half the actions fail
        scope="type == file", rules=[("big", "size > 1000", {})],
        evaluator="numpy", mutates=False, batch_size=4))
    eng.register(PolicyDefinition.from_config(
        "all", lambda e, params: True, scope="type == file",
        evaluator="numpy", mutates=False))
    reps = [eng.run(p, matching="full") for p in ("big", "all", "big")]
    assert [r.matched for r in reps] == [20, 30, 20]
    assert [r.succeeded for r in reps] == [10, 30, 10]
    engine = eng._tlabels["engine"]
    for r in reps:
        assert r.telemetry["counters"][
            _rows_series(engine, r.policy, "matched")] == r.matched
        assert r.telemetry["counters"][
            _rows_series(engine, r.policy, "actioned")] == r.succeeded
    values = cat.telemetry.counter_values()
    for policy in ("big", "all"):
        mine = [r for r in reps if r.policy == policy]
        assert values[_rows_series(engine, policy, "matched")] == \
            sum(r.matched for r in mine)
        assert values[_rows_series(engine, policy, "actioned")] == \
            sum(r.succeeded for r in mine)
    prom = parse_prometheus(cat.telemetry.render_prometheus())
    assert prom[_rows_series(engine, "big", "actioned")] == 20


def test_run_plan_span_carries_the_rows_planned():
    fs, _ = _fs(30)
    cat = Catalog()
    eng = _engine(fs, cat, "numpy")
    rep = eng.run("p", matching="full")
    [plan] = [c for c in rep.telemetry["spans"]["children"]
              if c["name"] == "run.plan"]
    assert plan["attrs"]["rows"] == rep.matched == 30


def _annotations(trace_dir):
    """The program's profiler annotations in a recorded trace:
    ``{name: (start_ns, end_ns)}``."""
    import glob
    import os
    from jax.profiler import ProfileData
    [path] = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                    "*", "*.xplane.pb"))
    return {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("rbh.")}


def test_spans_appear_nested_in_the_profiler_trace(tmp_path):
    import jax
    reg = MetricRegistry()
    off = MetricRegistry(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with reg.trace("outer"):
            with span("inner"):
                pass
        with off.trace("disabled"):
            pass
    finally:
        jax.profiler.stop_trace()
    ann = _annotations(tmp_path)
    assert set(ann) == {"rbh.outer", "rbh.inner"}
    (o0, o1), (i0, i1) = ann["rbh.outer"], ann["rbh.inner"]
    assert o0 <= i0 < i1 <= o1


# -- alerts (satellite: persistent handle + alerts_fired) ----------------------
def test_alert_log_persistent_handle_and_counter(tmp_path):
    fs, proj = _fs(5)
    cat = Catalog()
    log = tmp_path / "alerts.log"
    with AlertManager(str(log), telemetry=cat.telemetry) as mgr:
        mgr.add_rule(AlertRule("big", "size > 250"))
        cat.add_entry_hook(mgr.on_entry)
        Scanner(fs, cat).scan()
        assert mgr._fh is not None          # lazy-opened once, kept open
        fired = len(mgr.fired)
        assert fired > 0
        lines = log.read_text().strip().splitlines()
        assert len(lines) == fired          # flushed per record
    assert mgr._fh is None                  # context manager closed it
    vals = cat.telemetry.counter_values()
    assert vals['alerts_fired{rule="big"}'] == fired
    # firing after close lazily reopens
    f = fs.create(proj, "huge.bin", owner="u0")
    fs.write(f, 10_000)
    Scanner(fs, cat).scan()
    assert len(mgr.fired) > fired
    mgr.close()
