"""The HSM archive/release deployment, cell ``lustre_hsm.sweep``, at a size
the CPU holds: its runs against the plain reference, the program's
``policy_rows`` counter against the reference's plans, its per-policy
span readers on a traced run, and those readers on hand-made run trees
of mixed policies (``None`` where no run of the policy has the span)."""
import json
import os

import pytest

from bench_helpers import ROOT, no_cache, tiny_run  # noqa: F401
from bench import data, deploy, ops, reference as ref
from bench.harness import OpRecord, RunRecord, reader

CELL = "lustre_hsm.sweep"
ENTRIES = 20_000
SEED = 3017000001
POLICIES = ("cleanup", "lhsm_archive", "lhsm_release")
READERS = ("archive_run_ms", "release_run_ms", "archive_plan_ms",
           "archive_act_gather_ms", "archive_plan_ns_per_row")


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _cfg():
    return _load("bench", "configs", "lustre_hsm.json")


# -- the cell's files -------------------------------------------------------------

def test_cell_is_declared_with_its_metrics():
    bench = _load("BENCHMARK.json")
    [cell] = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("lustre_hsm", "sweep", 1)
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", [CELL])}
    assert listed == {"setup_s", "policy_run_s", "compiles_in_window",
                      "match_kernel_ms", "match_kernel_roofline", *READERS}
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "policy_run_s"


def test_configuration_keeps_the_scratch_laws_and_names_its_policies():
    cfg = _cfg()
    purge = _load("bench", "configs", "scratch_purge.json")
    assert cfg["catalog"] == purge["catalog"]
    assert cfg["entries"] == purge["entries"]
    pols = deploy.policies(cfg)
    assert tuple(pols) == POLICIES
    assert pols["cleanup"] == purge["policy"]
    assert pols["lhsm_archive"]["scope"] == \
        "type == file and (hsm_state == none or hsm_state == dirty)"
    assert pols["lhsm_release"]["scope"] == \
        "type == file and hsm_state == archived"
    for p in pols.values():
        assert (p["batch_size"], p["mutates"], p["evaluator"],
                p["matching"]) == (4096, False, "policy_scan_mesh", "full")
    tcfg = _load("bench", "traffic", "sweep.json")
    assert [s.get("policy") for s in tcfg["step"]] == list(POLICIES)
    assert all(s["op"] == "policy_run" for s in tcfg["step"])
    assert tcfg["limits"] == {"mismatches": 0}


# -- runs of the cell -------------------------------------------------------------

@pytest.fixture(scope="module")
def sweep():
    """One untraced run of the cell with the bf16 control, recording the
    policy of each compared answer and every run's ``RunReport``."""
    import repro.launch.compile_cache as cc
    from repro.core import PolicyEngine
    compared, reports = [], []
    compare, run = ops.compare, PolicyEngine.run

    def compare_policy(st, req, got, want, vol_limit):
        compared.append(req.get("policy"))
        return compare(st, req, got, want, vol_limit)

    def run_report(self, *a, **kw):
        rep = run(self, *a, **kw)
        reports.append(rep)
        return rep
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cc, "enable_compile_cache", lambda: "off")
        mp.setattr(ops, "compare", compare_policy)
        mp.setattr(PolicyEngine, "run", run_report)
        out = tiny_run(CELL, seed=SEED, controls=("bf16",), entries=ENTRIES)
    return out, compared, reports


def test_sweep_is_correct_and_compares_every_policy(sweep):
    out, compared, _ = sweep
    assert out.result["correct"], out.checks
    assert out.result["failed"] == 0
    assert out.checks["mismatches"]["value"] == 0
    assert out.checks["fallbacks"]["value"] == 0
    # the program's answers and the control's, on the same sample
    assert set(compared) == set(POLICIES)
    assert set(out.result["metrics"]) == {"setup_s", "policy_run_s"}


def test_sweep_bf16_control_fails_the_limit(sweep):
    out, _, _ = sweep
    control = out.controls["bf16"]
    assert control["correct"] is False, control
    assert control["mismatches"] > out.checks["mismatches"]["limit"]


def test_policy_rows_counter_equals_the_reference_plans(sweep):
    _, _, reports = sweep
    st = data.generate(_cfg()["catalog"], ENTRIES, SEED)
    want = {name: ref.plan(st, pol)[0].size
            for name, pol in deploy.policies(_cfg()).items()}
    assert set(r.policy for r in reports) == set(POLICIES)
    for r in reports:
        rows = {k: v for k, v in r.telemetry["counters"].items()
                if k.startswith("policy_rows{")}
        assert rows == {
            f'policy_rows{{engine="engine0",policy="{r.policy}",'
            f'stage="{stage}"}}': want[r.policy]
            for stage in ("matched", "actioned")}
        assert r.matched == r.succeeded == want[r.policy]
        [plan] = [c for c in r.telemetry["spans"]["children"]
                  if c["name"] == "run.plan"]
        assert plan["attrs"]["rows"] == want[r.policy]


def test_traced_sweep_reports_every_policy_reader(no_cache):
    out = tiny_run(CELL, seed=SEED + 1, trace=True, entries=ENTRIES)
    assert out.result["correct"], out.checks
    got = out.result["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    for name in READERS:
        assert got[name]["value"] > 0, name
    assert got["archive_run_ms"]["value"] > got["archive_plan_ms"]["value"]


# -- the readers on hand-made trees -------------------------------------------------

def _span(name, secs, children=(), **attrs):
    out = {"name": name, "elapsed_s": secs}
    if attrs:
        out["attrs"] = attrs
    if children:
        out["children"] = list(children)
    return out


def _run(policy, total, plan=None, gathers=(), rows=None):
    """A run tree; ``plan=None`` is a run that matched nothing, and
    ``rows=None`` a ``run.plan`` span without the attribute."""
    kids = [_span("run.ingest", 0.001), _span("run.match", 0.01)]
    if plan is not None:
        kids.append(_span("run.plan", plan) if rows is None
                    else _span("run.plan", plan, rows=rows))
        kids.append(_span("run.act", sum(gathers), [
            _span("run.act.gather", g, rows=4096) for g in gathers]))
    return _span("run", total, kids, policy=policy, trigger="manual")


def _record(trees):
    ops_ = [OpRecord("policy_run", {"op": "policy_run"}, 0.0, 0.0, 1.0, 0,
                     spans=t) for t in trees]
    return RunRecord(CELL, {}, {}, 1.0, 1.0, ops_, None, None, [])


MIXED = [_run("cleanup", 0.3, 0.02, [0.1], rows=400),
         _run("lhsm_archive", 4.0, 0.6, [1.0, 1.5], rows=5_000_000),
         _run("lhsm_release", 0.5, 0.05, [0.2], rows=700),
         _run("cleanup", 0.4, 0.02, [0.1], rows=400),
         _run("lhsm_archive", 5.0, 1.0, [2.5], rows=5_000_000),
         _run("lhsm_release", 0.7, 0.05, [0.2], rows=700)]


@pytest.mark.parametrize("metric,want", [
    ("archive_run_ms", 4500.0),             # (4.0 + 5.0) / 2
    ("release_run_ms", 600.0),              # (0.5 + 0.7) / 2
    ("archive_plan_ms", 800.0),             # (0.6 + 1.0) / 2
    ("archive_act_gather_ms", 2500.0),      # (1.0 + 1.5 + 2.5) / 2
    ("archive_plan_ns_per_row", 160.0),     # 1.6 s / 10M rows
])
def test_policy_readers_on_mixed_trees(metric, want):
    assert reader(metric)(_record(MIXED)) == pytest.approx(want)


@pytest.mark.parametrize("metric", READERS)
def test_policy_readers_read_none_without_their_policy(metric):
    read = reader(metric)
    assert read(_record([])) is None
    cleanup = [t for t in MIXED if t["attrs"]["policy"] == "cleanup"]
    assert read(_record(cleanup)) is None


@pytest.mark.parametrize("metric", ["archive_plan_ms", "archive_act_gather_ms",
                                    "archive_plan_ns_per_row"])
def test_plan_readers_read_none_where_no_archive_run_planned(metric):
    # an archive run that matched nothing has no plan and no act spans
    trees = [_run("lhsm_archive", 0.2),
             _run("lhsm_release", 0.5, 0.05, [0.2], rows=700)]
    assert reader(metric)(_record(trees)) is None
    assert reader("archive_run_ms")(_record(trees)) == pytest.approx(200.0)


def test_plan_per_row_needs_the_rows_attribute():
    read = reader("archive_plan_ns_per_row")
    before = [_run("lhsm_archive", 4.0, 0.6, [1.0])]      # a program
    assert read(_record(before)) is None                  # without `rows`
    assert reader("archive_plan_ms")(_record(before)) == pytest.approx(600.0)
