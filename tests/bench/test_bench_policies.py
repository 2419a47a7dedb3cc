"""A configuration of several named policies, run in rotation through the
harness at a size the CPU holds, and the plain reference's ``hsm_state``
criteria against the program's numpy evaluator. A configuration of one
``policy`` keeps every request, sampled index and roofline byte count it
had before policies could be named."""
import json
import os
from collections import defaultdict

import numpy as np
import pytest

from bench_helpers import FIXTURES, no_cache, tiny_run  # noqa: F401
from bench import data, deploy, harness, ops, reference as ref, roofline
from bench.generator import Traffic

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
N = 6000
ROTATION = "hsm_fixture.rotation"
STATES = ("none", "dirty", "archiving", "archived", "released", "restoring",
          "lost")


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _purge_cfg():
    return _load(ROOT, "bench", "configs", "scratch_purge.json")


def _hsm_cfg():
    return _load(FIXTURES, "configs", "hsm_fixture.json")


# -- the rotation run -----------------------------------------------------------

@pytest.fixture(scope="module")
def rotation():
    """One run of the fixture cell with the bf16 control, recording each
    policy run's matches and the policy of each compared answer."""
    import repro.launch.compile_cache as cc
    matched, compared = defaultdict(list), []
    run, compare = ops.RUNNERS["policy_run"], ops.compare

    def run_policy(dep, req):
        out = run(dep, req)
        matched[req["policy"]].append(out["answer"][0].size)
        return out

    def compare_policy(st, req, got, want, vol_limit):
        compared.append(req.get("policy"))
        return compare(st, req, got, want, vol_limit)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cc, "enable_compile_cache", lambda: "off")
        mp.setitem(ops.RUNNERS, "policy_run", run_policy)
        mp.setattr(ops, "compare", compare_policy)
        out = tiny_run(ROTATION, controls=("bf16",))
    return out, matched, compared


def test_rotation_run_is_correct(rotation):
    out, _, _ = rotation
    assert out.result["correct"], out.checks
    assert out.result["failed"] == 0
    assert out.checks["mismatches"]["value"] == 0
    assert out.checks["fallbacks"]["value"] == 0


def test_rotation_compares_every_policy(rotation):
    _, _, compared = rotation
    names = {p["name"] for p in _hsm_cfg()["policies"]}
    # the program's answers and the control's, on the same sample
    assert set(compared) == names


def test_rotation_runs_every_policy_in_turn(rotation):
    out, matched, _ = rotation
    runs = {k: len(v) for k, v in matched.items()}
    assert set(runs) == {"cleanup", "lhsm_archive", "lhsm_release"}
    assert max(runs.values()) - min(runs.values()) <= 1
    # no churn: every run of a policy matches the same rows
    assert all(len(set(v)) == 1 for v in matched.values())
    share = {k: v[0] / 20_000 for k, v in matched.items()}
    assert 0 < share["cleanup"] < share["lhsm_release"] \
        < share["lhsm_archive"]
    assert set(out.result["metrics"]) == {"setup_s", "policy_run_s"}


def test_rotation_bf16_control_fails_a_limit(rotation):
    out, _, _ = rotation
    control = out.controls["bf16"]
    assert control["correct"] is False, control
    assert control["mismatches"] > out.checks["mismatches"]["limit"]


@pytest.mark.parametrize("workload", [ROTATION, "scratch_purge.churn1"])
def test_traced_run_reads_no_compile_in_window(no_cache, workload):
    out = tiny_run(workload, trace=True)
    assert out.result["correct"], out.checks
    assert out.result["metrics"]["compiles_in_window"]["value"] == 0


# -- hsm_state criteria against the program ---------------------------------

@pytest.fixture(scope="module")
def hsm_catalog():
    from repro.core import PolicyEngine
    cfg = _purge_cfg()
    st = data.generate(cfg["catalog"], N, 424242)
    cat = deploy.load_catalog(st, cfg["catalog"]["shards"])
    return st, PolicyEngine(cat, clock=lambda: st.now), deploy.Recorder()


def _numpy_run(eng, rec, name, pol):
    from repro.core import PolicyDefinition
    eng.register(PolicyDefinition.from_config(
        name=name, action=rec, scope=pol["scope"],
        rules=[(n, c, {"rule": r}) for r, (n, c) in enumerate(pol["rules"])],
        sort_by=pol["sort_by"], mutates=False, batch_size=pol["batch_size"]))
    rep = eng.run(name, evaluator="numpy", matching="full")
    assert rep.evaluator == "numpy"
    return rec.drain()


CRITERIA = [f"hsm_state {op} {s}" for s in STATES for op in ("==", "!=")] + [
    "type == file and (hsm_state == none or hsm_state == dirty)",
    "hsm_state == archived and last_access > 30d",
    "hsm_state == released or size > 1GB",
    "(hsm_state != none and hsm_state != dirty) or type == dir",
    "not hsm_state == archived and owner == 'user3'",
]


@pytest.mark.parametrize("criteria", CRITERIA)
def test_hsm_mask_equals_the_numpy_evaluator(hsm_catalog, criteria):
    st, eng, rec = hsm_catalog
    pol = {"scope": criteria, "rules": [["all", "size >= 0"]],
           "sort_by": "atime", "batch_size": N}
    fids, _ = _numpy_run(eng, rec, criteria, pol)
    want = st.fid[ref.mask(ref.parse(criteria), st)]
    assert np.array_equal(np.sort(fids), want)
    drawn = STATES[: len(_purge_cfg()["catalog"]["hsm_state_p"])]
    if len(criteria.split()) == 3 and criteria.split()[2] in drawn:
        assert 0 < want.size < N


@pytest.mark.parametrize("name", ["cleanup", "lhsm_archive", "lhsm_release"])
def test_hsm_policy_plan_equals_the_numpy_engine(hsm_catalog, name):
    st, eng, rec = hsm_catalog
    pol = dict(deploy.policies(_hsm_cfg())[name], batch_size=64)
    fids, rules = _numpy_run(eng, rec, f"plan-{name}", pol)
    want_f, want_r = ref.plan(st, pol)
    assert fids.size > 50
    assert np.array_equal(fids, want_f) and np.array_equal(rules, want_r)


@pytest.mark.parametrize("criteria", ["hsm_state > none",
                                      "hsm_state == offline"])
def test_reference_refuses_an_hsm_criterion_it_cannot_read(criteria):
    st = data.generate(_purge_cfg()["catalog"], 100, 1)
    with pytest.raises(ValueError):
        ref.mask(ref.parse(criteria), st)


# -- a configuration of one policy is read as before ----------------------------

def _old_sample(records, n, seed):
    """The sampling rule before policies could be named: one answer of
    each op."""
    ok = [i for i, r in enumerate(records) if r.error is None]
    if not ok:
        return []
    rng = data.rng_for(seed, data.STREAM_CHECK)
    order = rng.permutation(ok).tolist()
    pick = set(order[:n])
    seen = set()
    for i in order:
        if records[i].op not in seen:
            seen.add(records[i].op)
            pick.add(i)
    pick.add(ok[-1])
    return sorted(pick)


def _records(ops_, errors=()):
    return [harness.OpRecord(op, {"op": op}, 0.0, 0.0, 0.0, 0,
                             error="x" if i in errors else None)
            for i, op in enumerate(ops_)]


@pytest.mark.parametrize("seed", [1, 20240611, 2 ** 31 + 5])
@pytest.mark.parametrize("kind", ["policy_runs", "queries"])
def test_sample_keeps_the_single_policy_picks(seed, kind):
    if kind == "policy_runs":
        recs = _records(["policy_run"] * 90, errors={3, 40})
    else:
        rng = np.random.default_rng(seed % 1000)
        recs = _records(rng.choice(["du", "find", "top_files", "report_user",
                                    "top_users"], 300).tolist(), errors={7})
    for n in (0, 5, 60):
        assert harness.sample(recs, n, seed) == _old_sample(recs, n, seed)


def test_sample_takes_one_answer_of_each_policy():
    recs = [harness.OpRecord("policy_run", {"op": "policy_run", "policy": p},
                             0.0, 0.0, 0.0, 0)
            for _ in range(100) for p in ("a", "b", "c")]
    picks = harness.sample(recs, 1, 5)
    assert {recs[i].request["policy"] for i in picks} == {"a", "b", "c"}


def test_row_bytes_of_the_scratch_purge_policy_are_unchanged():
    st = data.generate(_purge_cfg()["catalog"], 20_000, 3)
    # type 1 B, owner 1 B, size 4 B, atime 4 B, as before hsm_state
    assert roofline.row_bytes(st, _purge_cfg()["policy"]) == 10
    pols = deploy.policies(_hsm_cfg())
    assert roofline.row_bytes(st, pols["cleanup"]) == 10
    # type 1 B, hsm_state 1 B (codes 0-4), mtime or atime 4 B
    assert roofline.policy_columns(pols["lhsm_archive"]) == {
        "is_dir", "hsm", "mtime"}
    assert roofline.row_bytes(st, pols["lhsm_archive"]) == 6
    assert roofline.row_bytes(st, pols["lhsm_release"]) == 6


def test_churn1_requests_name_no_policy():
    cfg = _purge_cfg()
    tcfg = _load(ROOT, "bench", "traffic", "churn1.json")
    t = Traffic(tcfg, cfg, [], 20_000, 7)
    reqs = [e.request for e in t.warmup() + t.step() if e.request]
    assert reqs and all(r == {"op": "policy_run"} for r in reqs)
    assert deploy.request_policy(cfg, reqs[0]) is cfg["policy"]
    assert list(deploy.policies(cfg)) == ["cleanup"]


def test_rotation_requests_name_their_policies_in_turn():
    cfg = _hsm_cfg()
    tcfg = _load(FIXTURES, "traffic", "rotation.json")
    t = Traffic(tcfg, cfg, [], 20_000, 7)
    names = [e.request["policy"] for e in t.step()]
    assert names == ["cleanup", "lhsm_archive", "lhsm_release"]
    assert [deploy.request_policy(cfg, {"op": "policy_run", "policy": n})
            ["name"] for n in names] == names


def _both(cfg):
    return dict(cfg, policy=cfg["policies"][0])


def _duplicate(cfg):
    return dict(cfg, policies=cfg["policies"] + cfg["policies"][:1])


@pytest.mark.parametrize("case", ["both_keys", "duplicate_names",
                                  "unnamed_run", "unknown_policy"])
def test_malformed_policy_setups_are_refused(case):
    cfg = _hsm_cfg()
    with pytest.raises(ValueError):
        if case == "both_keys":
            deploy.policies(_both(cfg))
        elif case == "duplicate_names":
            deploy.policies(_duplicate(cfg))
        elif case == "unnamed_run":
            deploy.request_policy(cfg, {"op": "policy_run"})
        else:
            deploy.request_policy(cfg, {"op": "policy_run",
                                        "policy": "purge"})


def test_a_rotation_naming_an_unknown_policy_fails_in_set_up(
        no_cache, monkeypatch):
    from bench import generator
    step = generator.Traffic.step

    def renamed(self):
        events = step(self)
        events[1].request["policy"] = "purge"
        return events
    monkeypatch.setattr(generator.Traffic, "step", renamed)
    with pytest.raises(RuntimeError, match="unknown policy 'purge'"):
        tiny_run(ROTATION)


def test_a_configuration_without_policies_has_none():
    cfg = _load(ROOT, "bench", "configs", "project_reports.json")
    assert deploy.policies(cfg) == {}


@pytest.mark.parametrize("fault", ["half_left_out", "answer_altered"])
def test_fault_is_caught_in_rotation(no_cache, monkeypatch, fault):
    # with no churn a state left unchanged changes nothing: that fault is
    # the churning cells' to catch
    from test_bench_faults import FAULTS
    FAULTS[fault](monkeypatch)
    out = tiny_run(ROTATION)
    assert not out.result["correct"], out.checks
