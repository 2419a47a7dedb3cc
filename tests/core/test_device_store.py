"""Device-resident sharded column store: differential + refresh contracts.

In-process tests run on whatever devices exist (a 1-device ``("shards",)``
mesh on bare CPU — the mesh path must be correct there too); the
multi-device differential runs in a subprocess under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (see conftest).
"""
import threading

import numpy as np
import pytest

from conftest import run_subprocess
from repro.core import (Catalog, DeviceColumnStore, Entry, FsType, HsmState,
                        PolicyDefinition, PolicyEngine, parse_expr)

NOW = float(2 ** 20)          # f32-exact "now"

CONDITIONS = [
    "size > 16M",
    "size <= 4M",
    "owner == 'user1'",
    "last_access > 1000s",
    "hsm_state == archived",
    "size > 8M or owner == 'user0'",
    "not (size <= 1M or last_access <= 500s)",
]


def _shards_mesh():
    from repro.launch.mesh import make_shards_mesh
    return make_shards_mesh()


def _random_catalog(rng, n, n_shards=8):
    cat = Catalog(n_shards=n_shards)
    cat.upsert_batch([Entry(
        fid=i + 1, name=f"f{i + 1}", path=f"/p/d{i % 5}/f{i + 1}",
        type=FsType.FILE if rng.random() < 0.9 else FsType.DIR,
        size=int(rng.integers(0, 2 ** 15)) * 1024,           # f32-exact
        blocks=int(rng.integers(0, 2 ** 10)),
        owner=f"user{int(rng.integers(0, 4))}",
        group=f"grp{int(rng.integers(0, 3))}",
        hsm_state=HsmState(int(rng.integers(0, 5))),
        atime=NOW - float(rng.integers(0, 10_000)),          # f32-exact
        mtime=NOW - float(rng.integers(0, 10_000)),
    ) for i in range(n)])
    return cat


def _random_policy(rng, action):
    n_rules = int(rng.integers(1, 4))
    conds = rng.choice(len(CONDITIONS), size=n_rules, replace=False)
    return PolicyDefinition.from_config(
        name="p", action=action,
        scope=["true", "type == file"][int(rng.integers(0, 2))],
        rules=[(f"r{i}", CONDITIONS[int(c)], {"tag": f"r{i}"})
               for i, c in enumerate(conds)],
        sort_by=["atime", "size", "mtime"][int(rng.integers(0, 3))],
        sort_desc=bool(rng.integers(0, 2)),
        n_threads=1, batch_size=64, mutates=False)


class BatchRecorder:
    def __init__(self):
        self.lock = threading.Lock()
        self.calls = []

        def action_batch(batch, params):
            with self.lock:
                self.calls.extend(batch.fids.tolist())
            return [True] * len(batch)

        self.action_batch = action_batch

    def __call__(self, e, params):
        with self.lock:
            self.calls.append(e.fid)
        return True


def _engine_with_store(cat, policy, clock_t=NOW):
    eng = PolicyEngine(cat, clock=lambda: clock_t)
    eng.register(policy)
    eng.attach_device_store(DeviceColumnStore(cat, _shards_mesh()))
    return eng


# -- differential: mesh == single-launch == numpy -----------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mesh_matches_numpy_and_single_launch(seed):
    rng = np.random.default_rng(seed)
    cat = _random_catalog(rng, 500)
    results = {}
    for evaluator in ("numpy", "policy_scan", "policy_scan_mesh"):
        rec = BatchRecorder()
        policy = _random_policy(np.random.default_rng(seed + 100), rec)
        eng = _engine_with_store(cat, policy)
        r = eng.run("p", evaluator=evaluator)
        assert r.evaluator == evaluator, r.fallback_reason
        assert r.fallback_reason == ""
        results[evaluator] = (r.matched, r.succeeded, r.volume,
                              list(rec.calls))
    assert results["policy_scan_mesh"] == results["numpy"]
    assert results["policy_scan"] == results["numpy"]


@pytest.mark.parametrize("seed", [0, 1])
def test_mesh_differential_across_churn_rounds(seed):
    """Warm store (delta-scatter refreshed) keeps actioning the exact
    sequence a cold numpy scan of the same catalog state produces."""
    rng = np.random.default_rng(seed + 50)
    cat = _random_catalog(rng, 600)
    rec = BatchRecorder()
    policy = _random_policy(np.random.default_rng(seed + 150), rec)
    eng = _engine_with_store(cat, policy)
    eng.run("p", evaluator="policy_scan_mesh")       # cold upload
    store = eng.device_store
    live = np.arange(1, 601)
    for round_i in range(3):
        upd = rng.choice(live, size=40, replace=False)
        cat.update_fields_batch(
            upd.tolist(), size=int(rng.integers(0, 2 ** 15)) * 1024,
            atime=NOW - float(rng.integers(0, 10_000)))
        before = store.delta_refreshes
        rec.calls.clear()
        r_mesh = eng.run("p", evaluator="policy_scan_mesh")
        mesh_calls = list(rec.calls)
        assert store.delta_refreshes > before     # warm path: scatter, not restack
        rec.calls.clear()
        r_np = eng.run("p", evaluator="numpy")
        assert r_mesh.matched == r_np.matched
        assert mesh_calls == list(rec.calls), f"round {round_i}"


# -- refresh modes ------------------------------------------------------------

def test_scatter_refresh_equals_cold_upload_after_churn():
    rng = np.random.default_rng(7)
    cat = _random_catalog(rng, 400)
    expr = parse_expr("size > 8M and last_access > 2000s")
    warm = DeviceColumnStore(cat, _shards_mesh())
    warm.refresh()                                   # cold upload now
    upd = rng.choice(np.arange(1, 401), size=30, replace=False)
    cat.update_fields_batch(upd.tolist(), size=100 << 20, atime=NOW - 5000.0)
    fids_warm, agg_warm = warm.scan(expr, NOW)
    assert warm.delta_refreshes > 0 and warm.rows_scattered >= 30
    cold = DeviceColumnStore(cat, _shards_mesh())    # fresh: full upload
    fids_cold, agg_cold = cold.scan(expr, NOW)
    assert cold.delta_refreshes == 0 and cold.full_uploads > 0
    assert sorted(fids_warm.tolist()) == sorted(fids_cold.tolist())
    assert agg_warm["count"] == agg_cold["count"]
    assert agg_warm["volume"] == agg_cold["volume"]


def test_add_remove_rows_forces_full_reupload():
    rng = np.random.default_rng(9)
    cat = _random_catalog(rng, 300)
    expr = parse_expr("size > 1M")
    store = DeviceColumnStore(cat, _shards_mesh())
    store.scan(expr, NOW)
    uploads0 = store.full_uploads
    cat.remove(11)
    cat.upsert(Entry(fid=5001, name="n", path="/p/n", type=FsType.FILE,
                     size=64 << 20, atime=NOW - 100.0))
    fids, _ = store.scan(expr, NOW)
    assert store.full_uploads > uploads0             # structural fallback
    ref = cat.arrays()
    ref_fids = ref["fid"][expr.mask(ref, cat.strings, NOW)]
    assert sorted(fids.tolist()) == sorted(ref_fids.tolist())
    assert 11 not in fids.tolist() and 5001 in fids.tolist()


def test_churn_threshold_falls_back_to_full_upload():
    rng = np.random.default_rng(11)
    cat = _random_catalog(rng, 200)
    store = DeviceColumnStore(cat, _shards_mesh(), refresh_frac=0.05)
    store.refresh()
    # churn far above 5% of every group's rows
    cat.update_fields_batch(list(range(1, 150)), size=99 << 20)
    stats = store.refresh()
    assert stats["delta"] == 0 and stats["full"] > 0
    fids, _ = store.scan(parse_expr("size > 90M"), NOW)
    assert sorted(fids.tolist()) == list(range(1, 150))


def test_growth_repads_and_stays_correct():
    rng = np.random.default_rng(13)
    cat = _random_catalog(rng, 100)
    store = DeviceColumnStore(cat, _shards_mesh(), tile=128)
    store.refresh()
    rp0 = store._rp
    cat.upsert_batch([Entry(fid=10_000 + i, name=f"g{i}", path=f"/p/g{i}",
                            type=FsType.FILE, size=2 << 20,
                            atime=NOW - 10.0) for i in range(3000)])
    fids, _ = store.scan(parse_expr("size > 1M"), NOW)
    assert store._rp > rp0
    ref = cat.arrays()
    ref_fids = ref["fid"][parse_expr("size > 1M").mask(ref, cat.strings, NOW)]
    assert sorted(fids.tolist()) == sorted(ref_fids.tolist())


def test_fresh_store_skips_upload_when_quiet():
    cat = _random_catalog(np.random.default_rng(15), 150)
    store = DeviceColumnStore(cat, _shards_mesh())
    store.refresh()
    stats = store.refresh()                          # no churn in between
    assert stats == {"full": 0, "delta": 0,
                     "fresh": store.n_devices, "padded": 0}


# -- ops-layer routing --------------------------------------------------------

def test_scan_catalog_routes_through_store():
    from repro.kernels.policy_scan.ops import scan_catalog
    cat = _random_catalog(np.random.default_rng(17), 250)
    expr = parse_expr("size > 4M and last_access > 1000s")
    store = DeviceColumnStore(cat, _shards_mesh())
    fids_store, agg_store = scan_catalog(cat, expr, NOW, store=store)
    fids_up, agg_up = scan_catalog(cat, expr, NOW, use_kernel=False)
    assert sorted(fids_store.tolist()) == sorted(fids_up.tolist())
    assert agg_store["count"] == agg_up["count"]
    assert agg_store["volume"] == agg_up["volume"]
    assert agg_store["size_profile"] == agg_up["size_profile"]


def test_match_programs_mesh_agrees_with_match_programs():
    from repro.core.policy import all_of, any_of
    from repro.kernels.policy_scan.ops import (match_programs,
                                               match_programs_mesh)
    rng = np.random.default_rng(19)
    cat = _random_catalog(rng, 350)
    policy = _random_policy(np.random.default_rng(20), None)
    rule_exprs = [r.condition for r in policy.rules]
    exprs = [all_of([policy.scope, any_of(rule_exprs)])] + rule_exprs
    store = DeviceColumnStore(cat, _shards_mesh())
    mesh = match_programs_mesh(store, exprs, NOW)
    masks, agg, rule_idx = match_programs(cat.arrays(), exprs, cat.strings,
                                          NOW, use_kernel=False)
    fids, sizes, _sort, ridx = mesh.plan(policy.sort_by)
    arrays = cat.arrays()
    ref_fids = arrays["fid"][masks[0]]
    order = np.argsort(fids)
    ref_order = np.argsort(ref_fids)
    np.testing.assert_array_equal(fids[order], ref_fids[ref_order])
    np.testing.assert_array_equal(sizes[order],
                                  arrays["size"][masks[0]][ref_order])
    np.testing.assert_array_equal(ridx[order],
                                  rule_idx[masks[0]][ref_order])
    assert mesh.agg["count"] == agg["count"]
    assert mesh.agg["rule_count"] == agg["rule_count"]


def test_store_rejects_foreign_catalog_and_missing_axis():
    from repro.core.policy import PolicyError
    cat = _random_catalog(np.random.default_rng(23), 50)
    other = _random_catalog(np.random.default_rng(24), 50)
    eng = PolicyEngine(cat)
    store = DeviceColumnStore(other, _shards_mesh())
    with pytest.raises(PolicyError):
        eng.attach_device_store(store)
    from repro.launch.mesh import make_mesh
    with pytest.raises(PolicyError):
        DeviceColumnStore(cat, make_mesh((1,), ("data",)))


# -- multi-device (subprocess: 8 fake XLA devices) ----------------------------

@pytest.mark.slow
def test_mesh_differential_on_eight_devices():
    out = run_subprocess("""
import numpy as np
from repro.core import (Catalog, DeviceColumnStore, Entry, FsType,
                        PolicyDefinition, PolicyEngine)
from repro.launch.mesh import make_shards_mesh

NOW = float(2 ** 20)
rng = np.random.default_rng(0)
cat = Catalog(n_shards=16)
cat.upsert_batch([Entry(fid=i + 1, name=f"f{i}", path=f"/p/f{i}",
                        type=FsType.FILE,
                        size=int(rng.integers(0, 2 ** 15)) * 1024,
                        owner=f"user{i % 4}",
                        atime=NOW - float(rng.integers(0, 10_000)))
                  for i in range(3000)])
acted = []
def act(e, p): return True
act.action_batch = lambda b, p: (acted.extend(b.fids.tolist()),
                                 [True] * len(b))[1]
eng = PolicyEngine(cat, clock=lambda: NOW)
eng.register(PolicyDefinition.from_config(
    name="p", action=act, scope="type == file",
    rules=[("big", "size > 16M", {}), ("cold", "last_access > 5000s", {})],
    sort_by="atime", mutates=False))
mesh = make_shards_mesh(8)
assert mesh.devices.size == 8
store = DeviceColumnStore(cat, mesh)
eng.attach_device_store(store)
r = eng.run("p", evaluator="policy_scan_mesh")
assert r.evaluator == "policy_scan_mesh" and not r.fallback_reason
mesh_calls = list(acted); acted.clear()
rn = eng.run("p", evaluator="numpy")
assert r.matched == rn.matched and mesh_calls == acted
# warm delta refresh on every device's group
cat.update_fields_batch(list(range(1, 3000, 37)), size=200 << 20)
acted.clear()
r2 = eng.run("p", evaluator="policy_scan_mesh")
assert store.delta_refreshes == 8        # every group scattered, none restacked
mesh_calls = list(acted); acted.clear()
eng.run("p", evaluator="numpy")
assert mesh_calls == acted
# kernel (interpret) under shard_map agrees too
fids_k, _ = store.scan(__import__("repro.core",
                                  fromlist=["parse_expr"]).parse_expr(
    "size > 16M"), NOW, use_kernel=True)
fids_r, _ = store.scan(__import__("repro.core",
                                  fromlist=["parse_expr"]).parse_expr(
    "size > 16M"), NOW, use_kernel=False)
assert sorted(fids_k.tolist()) == sorted(fids_r.tolist())
print("OK", r.matched)
""")
    assert "OK" in out


# -- review regressions -------------------------------------------------------

def test_sort_by_fid_plans_and_parent_fid_falls_back():
    """fid is a valid mirror sort key; parent_fid (not mirrored) must
    degrade to the host path with a recorded reason, not crash."""
    cat = _random_catalog(np.random.default_rng(31), 200)
    rec = BatchRecorder()
    policy = PolicyDefinition.from_config(
        name="p", action=rec, scope="type == file",
        rules=[("any", "size >= 0", {})], sort_by="fid", mutates=False)
    eng = _engine_with_store(cat, policy)
    r = eng.run("p", evaluator="policy_scan_mesh")
    assert r.evaluator == "policy_scan_mesh" and not r.fallback_reason
    mesh_calls = list(rec.calls)
    rec.calls.clear()
    eng.run("p", evaluator="numpy")
    assert mesh_calls == rec.calls
    policy2 = PolicyDefinition.from_config(
        name="q", action=rec, scope="type == file",
        rules=[("any", "size >= 0", {})], sort_by="parent_fid",
        mutates=False)
    eng.register(policy2)
    r2 = eng.run("q", evaluator="policy_scan_mesh")
    assert r2.evaluator in ("policy_scan", "numpy")
    assert "policy_scan_mesh->" in r2.fallback_reason
    assert "sort_by" in r2.fallback_reason


def test_stale_mesh_match_plan_raises():
    from repro.core.policy import PolicyError
    cat = _random_catalog(np.random.default_rng(33), 150)
    store = DeviceColumnStore(cat, _shards_mesh())
    match = store.match([parse_expr("size >= 0")], NOW)
    cat.update_fields_batch([1, 2, 3], size=77 << 20)
    store.refresh()                      # mirrors mutated since the match
    with pytest.raises(PolicyError, match="stale"):
        match.plan("size")
    # a fresh match plans fine again
    store.match([parse_expr("size >= 0")], NOW).plan("size")


def test_scan_catalog_rejects_mismatched_store():
    from repro.core.policy import PolicyError
    from repro.kernels.policy_scan.ops import scan_catalog
    cat = _random_catalog(np.random.default_rng(35), 60)
    other = _random_catalog(np.random.default_rng(36), 60)
    store = DeviceColumnStore(other, _shards_mesh())
    with pytest.raises(PolicyError, match="different catalog"):
        scan_catalog(cat, parse_expr("size >= 0"), NOW, store=store)


def test_incremental_run_records_requested_evaluator_override():
    cat = _random_catalog(np.random.default_rng(37), 120)
    rec = BatchRecorder()
    policy = PolicyDefinition.from_config(
        name="p", action=rec, scope="type == file",
        rules=[("any", "size >= 0", {})], sort_by="atime", mutates=False)
    eng = _engine_with_store(cat, policy)
    eng.enable_incremental()
    eng.run("p")                                   # prime the cache
    eng.mark_dirty([1])
    r = eng.run("p", evaluator="policy_scan_mesh", matching="incremental")
    assert r.mode == "incremental" and r.evaluator == "numpy"
    assert "policy_scan_mesh->incremental" in r.fallback_reason


def test_trajectory_creates_missing_dir(tmp_path):
    import sys
    sys.path.insert(0, "/root/repo")
    from benchmarks.run import _append_trajectory
    out = tmp_path / "nested" / "traj"
    path = _append_trajectory(str(out), "bench_policy",
                              [("row", 1.0, "d")], True, 0.5)
    import json
    data = json.load(open(path))
    assert data["suite"] == "benchmarks.bench_policy"
    assert len(data["entries"]) == 1
    # appending accumulates
    _append_trajectory(str(out), "bench_policy", [("row", 2.0, "d")],
                       False, 0.5)
    assert len(json.load(open(path))["entries"]) == 2


def test_detach_unregisters_hook_and_store_stays_correct():
    cat = _random_catalog(np.random.default_rng(41), 100)
    store = DeviceColumnStore(cat, _shards_mesh())
    store.refresh()
    assert store._on_delta in cat._hooks
    store.detach()
    assert store._on_delta not in cat._hooks
    cat.update_fields(1, size=99 << 20)       # no dirty intake anymore
    assert all(not g.dirty for g in store._groups)
    # matching still works: hook-less mutations force cold full uploads
    fids, _ = store.scan(parse_expr("size > 90M"), NOW)
    assert fids.tolist() == [1]
    store.detach()                             # idempotent


def test_refresh_repads_when_group_outgrows_capacity_mid_refresh():
    """A snapshot that exceeds the padded capacity (concurrent insert
    race) must re-pad and retry, not crash the stack staging."""
    from repro.core.device_store import _RepadNeeded
    cat = _random_catalog(np.random.default_rng(43), 100)
    store = DeviceColumnStore(cat, _shards_mesh(), tile=128)
    store.refresh()
    # simulate the race: capacity says _rp, but the snapshot will see more
    # rows than refresh()'s initial need-check observed
    store._rp = store.tile                 # force an undersized capacity
    for g in store._groups:
        g.uploaded = False                 # every group must re-upload
    cat.upsert_batch([Entry(fid=20_000 + i, name=f"r{i}", path=f"/p/r{i}",
                            type=FsType.FILE, size=5 << 20,
                            atime=NOW - 1.0) for i in range(2000)])
    stats = store.refresh()                # would raise before the retry fix
    assert stats["full"] == store.n_devices
    fids, _ = store.scan(parse_expr("size > 4M"), NOW)
    ref = cat.arrays()
    ref_fids = ref["fid"][parse_expr("size > 4M").mask(ref, cat.strings, NOW)]
    assert sorted(fids.tolist()) == sorted(ref_fids.tolist())


# -- tracing: spans, byte counts and program names ------------------------------

def _spans(tree, name):
    own = [tree] if tree["name"] == name else []
    return own + [s for c in tree.get("children", [])
                  for s in _spans(c, name)]


def _traced_store_run():
    """A warm store-backed run after a 40-row update: its report and
    store."""
    cat = _random_catalog(np.random.default_rng(11), 400)
    policy = PolicyDefinition.from_config(
        name="p", action=BatchRecorder(), scope="type == file",
        rules=[("big", "size > 16M", {}), ("old", "last_access > 1000s", {})],
        sort_by="atime", n_threads=1, batch_size=64, mutates=False)
    eng = _engine_with_store(cat, policy)
    eng.run("p", evaluator="policy_scan_mesh")
    cat.update_fields_batch(list(range(1, 41)), size=32 << 20)
    rep = eng.run("p", evaluator="policy_scan_mesh")
    assert rep.evaluator == "policy_scan_mesh" and rep.matched > 64
    return rep, eng.device_store


def test_store_backed_run_tree_holds_the_layer_spans():
    rep, _ = _traced_store_run()
    tree = rep.telemetry["spans"]
    assert [c["name"] for c in tree["children"]] == [
        "run.ingest", "run.match", "run.plan", "run.act"]
    [refresh] = _spans(tree, "store.refresh")
    assert [c["name"] for c in refresh["children"]] == [
        "store.refresh.gather"]
    assert refresh["children"][0]["attrs"]["rows"] == 40
    [combine] = _spans(tree, "store.match.combine")
    assert [c["name"] for c in combine["children"]] == [
        "store.match.wait", "store.match.readback"]
    gathers = _spans(tree, "run.act.gather")
    assert len(gathers) == -(-rep.matched // 64)
    assert _spans(tree, "store.match.launch") == []


def test_readback_and_refresh_bytes_match_the_shapes():
    from repro.kernels.policy_scan.ref import N_AGG
    rep, store = _traced_store_run()
    tree = rep.telemetry["spans"]
    counters = rep.telemetry["counters"]
    label = f'{{store="{store._tlabels["store"]}"}}'
    rows = store.n_devices * store._rp
    # the engine's match (no fused aggregation) off the kernel path: a bool
    # mask0 and an i32 rule per padded row, and (R, N_AGG) f32 aggregates
    # for the 1 + 2 programs
    want = rows * (1 + 4) + 3 * N_AGG * 4
    [readback] = _spans(tree, "store.match.readback")
    assert readback["attrs"]["d2h_bytes"] == want
    assert counters["store_d2h_bytes" + label] == want
    # 40 dirty rows scatter as a 64-row bucket: i32 indices and one f32
    # value per block row; nothing else crosses the link
    h2d = 64 * 4 + 64 * store._block_rows() * 4
    [refresh] = _spans(tree, "store.refresh")
    assert refresh["attrs"]["h2d_bytes"] == h2d
    moved = {k: v for k, v in counters.items()
             if k.startswith("store_h2d_bytes{") and v}
    assert moved == {
        f'store_h2d_bytes{{mode="scatter",store="{store._tlabels["store"]}"}}':
            h2d}


def test_full_upload_bytes_count_the_padded_blocks():
    cat = _random_catalog(np.random.default_rng(12), 300)
    store = DeviceColumnStore(cat, _shards_mesh())
    with cat.telemetry.trace("outer") as outer:
        stats = store.refresh()
    assert stats["full"] == store.n_devices
    # every group ships its whole (block rows, padded rows) f32 block
    want = store.n_devices * store._block_rows() * store._rp * 4
    [refresh] = outer.children
    assert refresh.attrs["h2d_bytes"] == want
    assert store._h2d_series("full").value == want
    assert store._h2d_total() == want


def _call_store_program(name):
    """Run one of the store's lazily-jitted programs on a small block and
    return (jitted function, its arguments, its static keywords)."""
    import jax.numpy as jnp
    from repro.core import device_store as ds
    buf = jnp.zeros((1, 3, 256), jnp.float32)
    rows = np.arange(64, dtype=np.int32)
    if name == "store_scatter_rows":
        vals = np.ones((3, 64), np.float32)
        ds._scatter_rows(jnp.zeros_like(buf), rows, vals)
        return ds._SCATTER_FN, (buf, rows, vals), {}
    if name == "store_pad_block":
        ds._pad_block(jnp.zeros_like(buf), 128)
        return ds._PAD_BLOCK_FN, (buf,), {"pad": 128}
    if name == "store_scatter_row":
        vals = np.ones(64, np.float32)
        ds._scatter_row(jnp.zeros_like(buf), 1, rows, vals)
        return ds._SCATTER_ROW_FN, (buf, rows, vals), {"row": 1}
    vals = np.ones((3, 64), np.float32)
    ds._cube_scatter(jnp.zeros_like(buf), rows, vals)
    return ds._CUBE_SCATTER_FN, (buf, rows, vals), {}


@pytest.mark.parametrize("name", ["store_scatter_rows", "store_pad_block",
                                  "store_scatter_row", "store_cube_scatter"])
def test_store_programs_carry_their_names(name):
    fn, args, kw = _call_store_program(name)
    text = fn.lower(*args, **kw).as_text()
    assert f"module @jit_{name} " in text
