"""The generator and the arrivals are fixed by the seed, and every seed
draws the same work in another order."""
import json
import os
from collections import Counter

import numpy as np

import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
from bench import data, deploy
from bench.generator import Traffic, largest_remainder

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _load(kind, name):
    with open(os.path.join(ROOT, "bench", kind, f"{name}.json")) as f:
        return json.load(f)


def _schedule(seed, seconds=60.0, entries=20_000):
    cfg = _load("configs", "project_reports")
    tcfg = _load("traffic", "scoped_steady")
    t = Traffic(tcfg, cfg, deploy.subjects(cfg), entries, seed)
    return t.schedule(seconds)


def test_catalog_is_a_function_of_the_seed():
    cat = _load("configs", "scratch_purge")["catalog"]
    a = data.generate(cat, 5000, 2 ** 31 + 12345)
    b = data.generate(cat, 5000, 2 ** 31 + 12345)
    c = data.generate(cat, 5000, 7)
    for f in ("size", "blocks", "atime", "owner", "is_dir", "hsm"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.size, c.size)


def test_catalog_values_are_f32_exact_and_cover_every_group():
    cat = _load("configs", "scratch_purge")["catalog"]
    st = data.generate(cat, 5000, 3)
    for col in (st.size, st.blocks, st.atime):
        assert np.array_equal(col.astype(np.float32).astype(col.dtype), col)
    keys = set(zip(st.owner.tolist(), st.is_dir.tolist(), st.hsm.tolist()))
    assert len(keys) == cat["owners"] * 2 * len(cat["hsm_state_p"])


def test_arrivals_are_a_function_of_the_seed():
    a, b = _schedule(11), _schedule(11)
    assert [e.due for e in a] == [e.due for e in b]
    assert [e.request for e in a] == [e.request for e in b]
    for x, y in zip(a, b):
        if x.churn is not None:
            assert all(np.array_equal(p.fids, q.fids)
                       for p, q in zip(x.churn, y.churn))


def test_seeds_draw_the_same_work_in_another_order():
    tcfg = _load("traffic", "scoped_steady")
    a, b = _schedule(11), _schedule(2 ** 31 + 5)
    qa = [e for e in a if e.request]
    qb = [e for e in b if e.request]
    assert [e.due for e in qa] != [e.due for e in qb]
    kinds_a = Counter(e.request["op"] for e in qa[:200])
    kinds_b = Counter(e.request["op"] for e in qb[:200])
    mix = tcfg["mix"]
    share = {k: v / sum(mix.values()) for k, v in mix.items()}
    for k, s in share.items():
        ka = sum(v for op, v in kinds_a.items()
                 if op == k or (k == "profile"
                                and op in ("report_user", "top_users")))
        kb = sum(v for op, v in kinds_b.items()
                 if op == k or (k == "profile"
                                and op in ("report_user", "top_users")))
        assert ka == kb == round(s * 200)


def test_each_gap_block_keeps_the_offered_rate():
    tcfg = _load("traffic", "scoped_steady")
    rate, b = tcfg["rate_per_s"], tcfg["gap_block"]
    seconds = 2 * b / rate
    for seed in (1, 2 ** 31 + 9):
        dues = [e.due for e in _schedule(seed, seconds=seconds)
                if e.request]
        # one whole block of gaps spans b / rate seconds, to 1%
        assert abs(dues[b - 1] - b / rate) < 0.02 * b / rate


def test_largest_remainder_sums_to_the_total():
    w = np.arange(1, 45, dtype=float) ** -1.1
    c = largest_remainder(w, 100)
    assert c.sum() == 100 and (np.diff(c) <= 0).all()


def test_churn_is_a_function_of_the_seed_and_keeps_its_classes():
    cat = _load("configs", "scratch_purge")["catalog"]
    a = data.Churn(cat, 10_000, 9, 400, 20).batch()
    b = data.Churn(cat, 10_000, 9, 400, 20).batch()
    assert [c.fids.tolist() for c in a] == [c.fids.tolist() for c in b]
    assert sorted(c.size for c in a) == sorted(
        c.size for c in data.Churn(cat, 10_000, 10, 400, 20).batch())
    fids = np.concatenate([c.fids for c in a])
    assert np.unique(fids).size == 400
