"""The plain reference against the program's own host paths, at a size
the CPU holds: ``PolicyEngine`` with the numpy evaluator, and the
``Reports``/``ProfileCube`` host folds with ``GrantTable`` scoping."""
import json
import os

import numpy as np
import pytest

import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
from bench import data, deploy, ops, reference as ref

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
N = 6000


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def purge():
    from repro.core import PolicyDefinition, PolicyEngine
    cfg = _cfg("scratch_purge")
    st = data.generate(cfg["catalog"], N, 424242)
    cat = deploy.load_catalog(st, cfg["catalog"]["shards"])
    rec = deploy.Recorder()
    pol = cfg["policy"]
    eng = PolicyEngine(cat, clock=lambda: st.now)
    eng.register(PolicyDefinition.from_config(
        name=pol["name"], action=rec, scope=pol["scope"],
        rules=[(n, c, {"rule": r}) for r, (n, c) in enumerate(pol["rules"])],
        sort_by=pol["sort_by"], mutates=False, batch_size=64))
    return cfg, st, cat, eng, rec


def _run(eng, rec, name):
    rep = eng.run(name, evaluator="numpy", matching="full")
    assert rep.evaluator == "numpy"
    return rec.drain()


def test_plan_equals_the_numpy_engine(purge):
    cfg, st, cat, eng, rec = purge
    pol = dict(cfg["policy"], batch_size=64)
    fids, rules = _run(eng, rec, pol["name"])
    want_f, want_r = ref.plan(st, pol)
    assert fids.size > 50
    assert np.array_equal(fids, want_f) and np.array_equal(rules, want_r)


def test_plan_follows_churn(purge):
    cfg, st, cat, eng, rec = purge
    pol = dict(cfg["policy"], batch_size=64)
    st2 = st.copy()
    churn = data.Churn(cfg["catalog"], N, 5, 600, 20)
    for _ in range(2):
        calls = churn.batch()
        for c in calls:
            cat.update_fields_batch(c.fids.tolist(), **c.fields())
        data.apply_churn(st2, calls)
    fids, rules = _run(eng, rec, pol["name"])
    want_f, want_r = ref.plan(st2, pol)
    assert np.array_equal(fids, want_f) and np.array_equal(rules, want_r)
    assert not np.array_equal(want_f, ref.plan(st, pol)[0])


def test_bf16_plan_departs_from_the_reference(purge):
    cfg, st, *_ = purge
    a, _ = ref.plan(st, cfg["policy"])
    b, _ = ref.plan(st, cfg["policy"], "bf16")
    n = min(a.size, b.size)
    assert a.size != b.size or np.count_nonzero(a[:n] != b[:n]) > 0


@pytest.fixture(scope="module")
def reports():
    from repro.core import GrantTable
    from repro.core.profiles import ProfileCube
    from repro.core.reports import Reports
    cfg = _cfg("project_reports")
    st = data.generate(cfg["catalog"], N, 99)
    cat = deploy.load_catalog(st, cfg["catalog"]["shards"])
    subs = deploy.subjects(cfg)
    grants = GrantTable()
    for s in subs:
        grants.add_subject(s["name"], owners=s["owners"], groups=s["groups"],
                           subtrees=s["subtrees"])
    clock = lambda: st.now                                   # noqa: E731
    rep = Reports(cat, clock=clock).attach_grants(grants)
    cube = ProfileCube(cat, clock=clock)
    cube.attach()
    cube.attach_grants(grants)

    class Host:                       # the harness's view of a deployment
        pass
    dep = Host()
    dep.reports, dep.cube, dep.cfg = rep, cube, cfg
    return cfg, st, dep, subs


def _requests(cfg, subs):
    tpl = {"find_templates": [
        {"criteria": "size > {s} and last_access > {a}",
         "s": ["16KB", "1MB"], "a": ["7d", "30d"]},
        {"criteria": "type == file and size > {s} and last_mod > {a}",
         "s": ["64KB"], "a": ["90d"]}],
        "top_files": {"by": "size", "k": 20},
        "profile": {"report_user": 1, "top_users": 1, "top_users_k": 5}}
    rng = np.random.default_rng(3)
    out = []
    for s in (subs[0], subs[1], subs[33], subs[40], subs[42], subs[43]):
        for kind in ("du", "du", "find", "find", "top_files", "profile",
                     "profile", "profile"):
            req = ops.MAKERS[kind](rng, s, tpl, cfg["catalog"])
            req["subject"] = s
            out.append(req)
    return out


def test_reference_equals_the_host_folds(reports):
    cfg, st, dep, subs = reports
    seen = set()
    for req in _requests(cfg, subs):
        got = ops.RUNNERS[req["op"]](dep, req)["answer"]
        want = ops.reference(st, cfg, req)
        bad, err = ops.compare(st, req, got, want, 0.0)
        assert bad == 0 and err == 0.0, (req, got, want)
        seen.add(req["op"])
    assert seen == {"du", "find", "top_files", "report_user", "top_users"}


def test_bf16_answers_depart_from_the_reference(reports):
    cfg, st, dep, subs = reports
    bad_total, worst = 0, 0.0
    for req in _requests(cfg, subs):
        want = ops.reference(st, cfg, req)
        got = ops.reference(st, cfg, req, "bf16")
        bad, err = ops.compare(st, req, got, want, 0.0)
        bad_total += bad
        worst = max(worst, err)
    assert bad_total > 0 and worst > 1e-4


def test_subtree_and_visibility_follow_the_path_format():
    cfg = _cfg("project_reports")
    st = data.generate(cfg["catalog"], 3000, 1)
    paths = np.array([st.path(i) for i in range(st.n)])
    for prefix in ("/proj/grp3", "/proj/grp3/user23", "/proj/grp3/user23/d7",
                   "/proj", "/proj/grp30", "/other"):
        p = prefix.rstrip("/")
        want = (paths == p) | np.char.startswith(paths, p + "/")
        assert np.array_equal(ref.subtree(st, prefix), want), prefix
