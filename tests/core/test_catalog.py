import numpy as np
import pytest

from repro.core import Catalog, Entry, FsType, HsmState
from repro.core import catalog as catalog_mod


def _entry(fid, **kw):
    defaults = dict(parent_fid=1, name=f"f{fid}", path=f"/a/f{fid}",
                    type=FsType.FILE, size=fid * 100, blocks=fid * 100,
                    owner="foo", atime=1.0, mtime=1.0, ctime=1.0)
    defaults.update(kw)
    return Entry(fid=fid, **defaults)


def test_upsert_get_roundtrip():
    cat = Catalog(n_shards=3)
    e = _entry(42, owner="bar", pool="ssd", hsm_state=HsmState.ARCHIVED,
               xattrs={"k": "v"}, stripe_osts=(1, 2))
    cat.upsert(e)
    out = cat.get(42)
    assert out.owner == "bar" and out.pool == "ssd"
    assert out.hsm_state == HsmState.ARCHIVED
    assert out.xattrs == {"k": "v"} and out.stripe_osts == (1, 2)
    assert len(cat) == 1


def test_update_fields_and_remove():
    cat = Catalog(n_shards=2)
    cat.upsert(_entry(7))
    assert cat.update_fields(7, size=999, owner="baz")
    assert cat.get(7).size == 999 and cat.get(7).owner == "baz"
    assert cat.remove(7)
    assert cat.get(7) is None
    assert not cat.remove(7)


def test_vector_query():
    cat = Catalog(n_shards=4)
    for i in range(1, 101):
        cat.upsert(_entry(i, owner="foo" if i % 2 else "bar"))
    fids = cat.query_fids(lambda c: c["size"] > 5000)
    assert sorted(fids.tolist()) == list(range(51, 101))
    cols = cat.arrays()
    assert len(cols["_paths"]) == 100


def test_sqlite_persistence_roundtrip(tmp_path):
    db = str(tmp_path / "cat.db")
    cat = Catalog(n_shards=2, db_path=db)
    for i in range(1, 21):
        cat.upsert(_entry(i))
    cat.remove(5)
    # crash: new catalog from same file
    cat2 = Catalog(n_shards=2, db_path=db)
    n = cat2.load_from_db()
    assert n == 19
    assert cat2.get(5) is None and cat2.get(6).size == 600


def test_delta_hooks_fire():
    cat = Catalog(n_shards=1)
    deltas = []
    cat.add_delta_hook(lambda old, new: deltas.append((old, new)))
    cat.upsert(_entry(1))
    cat.update_fields(1, size=5)
    cat.remove(1)
    assert len(deltas) == 3
    assert deltas[0][0] is None and deltas[2][1] is None


def test_get_batch_roundtrip_and_missing():
    cat = Catalog(n_shards=3)
    for i in range(1, 41):
        cat.upsert(_entry(i, owner=f"u{i % 4}"))
    fids = [5, 999, 17, 2, 1000, 40]
    got = cat.get_batch(fids)
    assert got[1] is None and got[4] is None
    for fid, e in zip(fids, got):
        if e is not None:
            assert e.fid == fid
            # batch-built entries must equal scalar-built ones exactly
            assert e == cat.get(fid)


def test_get_batch_matches_get_for_all_fields():
    cat = Catalog(n_shards=2)
    cat.upsert(_entry(9, owner="bar", pool="ssd", hsm_state=HsmState.RELEASED,
                      xattrs={"k": "v"}, stripe_osts=(3, 1), dirty=True))
    (batch,) = cat.get_batch([9])
    assert batch == cat.get(9)
    assert batch.hsm_state is HsmState.RELEASED
    assert batch.type is FsType.FILE


def test_update_fields_batch_fires_hooks_and_returns_updated():
    cat = Catalog(n_shards=4)
    fired = []
    cat.add_delta_hook(lambda old, new: fired.append((old, new)))
    for i in range(1, 11):
        cat.upsert(_entry(i))
    fired.clear()
    updated = cat.update_fields_batch([3, 7, 999, 4], status="expired")
    assert sorted(updated) == [3, 4, 7]
    assert len(fired) == 3                       # one delta per updated entry
    for fid in (3, 4, 7):
        assert cat.get(fid).status == "expired"


def test_remove_batch():
    cat = Catalog(n_shards=2)
    for i in range(1, 11):
        cat.upsert(_entry(i))
    assert cat.remove_batch([2, 4, 999, 6]) == 3
    assert len(cat) == 7
    assert cat.get(4) is None


def test_column_slice_alignment():
    cat = Catalog(n_shards=4)
    for i in range(1, 21):
        cat.upsert(_entry(i))
    fids = [7, 300, 14, 1]
    cols, present = cat.column_slice(fids, ["size", "blocks"])
    assert present.tolist() == [True, False, True, True]
    assert cols["size"].tolist() == [700, 0, 1400, 100]
    assert cols["size"].dtype == np.int64


def test_column_batch_entry_free_view():
    from repro.core import ColumnBatch
    cat = Catalog(n_shards=3)
    for i in range(1, 21):
        cat.upsert(_entry(i, owner=f"u{i % 3}", pool="ssd" if i % 2 else ""))
    fids = [7, 300, 14, 1, 2]
    batch = cat.column_batch(fids)
    assert isinstance(batch, ColumnBatch) and len(batch) == 5
    assert batch.present.tolist() == [True, False, True, True, True]
    assert batch.fids.tolist() == [7, 0, 14, 1, 2]
    assert batch.size.tolist() == [700, 0, 1400, 100, 200]
    # lazy string decode through the interned codes
    assert batch.decode("owner") == ["u1", "", "u2", "u1", "u2"]
    assert batch.decode("pool") == ["ssd", "", "", "ssd", ""]
    # sub-batch slicing keeps alignment; bool masks select, not index
    sub = batch.take([0, 2])
    assert sub.fids.tolist() == [7, 14] and sub.present.all()
    assert sub.decode("owner") == ["u1", "u2"]
    masked = batch.take(batch.present)
    assert masked.fids.tolist() == [7, 14, 1, 2]
    # the materializing escape hatch equals get_batch
    assert batch.entries() == cat.get_batch(fids)


def test_column_batch_from_entries_matches_gather():
    from repro.core import ColumnBatch
    cat = Catalog(n_shards=2)
    for i in range(1, 11):
        cat.upsert(_entry(i, owner=f"u{i % 2}"))
    fids = [3, 99, 8]
    direct = cat.column_batch(fids)
    shim = ColumnBatch.from_entries(cat.get_batch(fids), cat.strings, cat)
    assert (shim.present == direct.present).all()
    for name in direct.cols:
        assert (shim.cols[name] == direct.cols[name]).all(), name


def test_catalog_version_bumps_on_every_mutation():
    cat = Catalog(n_shards=2)
    v = cat.version
    cat.upsert(_entry(1)); assert cat.version > v; v = cat.version
    cat.upsert_batch([_entry(2), _entry(3)]); assert cat.version > v
    v = cat.version
    cat.update_fields(1, size=5); assert cat.version > v; v = cat.version
    cat.update_fields_batch([2, 3], status="x"); assert cat.version > v
    v = cat.version
    cat.remove(1); assert cat.version > v; v = cat.version
    cat.remove_batch([2]); assert cat.version > v


def test_arrays_lazy_paths_still_correct():
    cat = Catalog(n_shards=3)
    for i in range(1, 16):
        cat.upsert(_entry(i))
    cols = cat.arrays()
    # _paths/_names materialize lazily but align with the numeric columns
    assert "_paths" in cols
    paths = cols["_paths"]
    assert len(paths) == len(cols["fid"])
    for fid, p in zip(cols["fid"].tolist(), paths):
        assert p == f"/a/f{fid}"


def test_arrays_cached_per_version():
    """Two arrays() calls at the same catalog version return the SAME
    cached object (no per-run shard concat); any mutation invalidates."""
    cat = Catalog(n_shards=3)
    for i in range(1, 21):
        cat.upsert(_entry(i))
    a = cat.arrays()
    b = cat.arrays()
    assert a is b
    # lazy string materialization does not invalidate the cache
    _ = a["_paths"]
    assert cat.arrays() is a
    cat.update_fields(3, size=123)
    c = cat.arrays()
    assert c is not a
    assert c["size"][np.nonzero(c["fid"] == 3)[0][0]] == 123
    assert cat.arrays() is c
    cat.remove(5)
    assert cat.arrays() is not c


# -- the fid index of batch lookups ---------------------------------------------

_BATCH_METHODS = ["gather_rows", "column_slice", "column_batch", "get_batch",
                  "update_fields_batch"]


def _by_dict(cat, fid):
    """(shard, row) of a fid by the ``_rows`` dict, the scalar authority."""
    shard = cat.shard_of(fid)
    return shard, shard._rows.get(fid)


def _batch_vs_dict(cat, method, fids, step):
    """Run one batch method on ``fids`` and what the ``_rows`` dict says it
    must give: (got, want), compared with dtypes."""
    fids = list(fids)
    if method == "get_batch":
        want = []
        for f in fids:
            shard, row = _by_dict(cat, f)
            want.append(None if row is None else shard._entry_at(row))
        return cat.get_batch(np.asarray(fids, dtype=np.int64)), want
    if method == "update_fields_batch":
        # the rows the batch patches must be exactly the dict's rows
        before = [{n: c.copy() for n, c in s._cols.items()}
                  for s in cat.shards]
        value = 10 ** 12 + step
        updated = cat.update_fields_batch(fids, blocks=value)
        want_rows = {(s.shard_id, r) for s, r in map(
            lambda f: _by_dict(cat, f), fids) if r is not None}
        got_rows = set()
        for s, old in zip(cat.shards, before):
            for name, col in s._cols.items():
                changed = np.nonzero(col[: old[name].size] != old[name])[0]
                if name == "blocks":
                    got_rows |= {(s.shard_id, int(r)) for r in changed}
                else:
                    assert not changed.size, name
        present = [f for f in fids if _by_dict(cat, f)[1] is not None]
        return ((sorted(updated), got_rows),
                (sorted(present), want_rows))
    names = [n for n, _ in catalog_mod._NUMERIC_COLUMNS]
    want_cols = {n: np.zeros(len(fids), dtype=dt)
                 for n, dt in catalog_mod._NUMERIC_COLUMNS}
    want_present = np.zeros(len(fids), dtype=bool)
    want_paths = [""] * len(fids)
    for i, f in enumerate(fids):
        shard, row = _by_dict(cat, f)
        if row is not None:
            want_present[i] = True
            want_paths[i] = shard._paths[row]
            for n in names:
                want_cols[n][i] = shard._cols[n][row]
    arr = np.asarray(fids, dtype=np.int64)
    if method == "column_slice":
        cols, present = cat.column_slice(arr, ["size", "owner", "atime"])
        want_cols = {n: want_cols[n] for n in ("size", "owner", "atime")}
    elif method == "gather_rows":
        cols, present = cat.gather_rows(arr)
        want_cols["_paths"] = want_paths
        cols["_names"] = None           # names follow paths' rule
    else:
        batch = cat.column_batch(arr)
        cols, present = batch.cols, batch.present

    def norm(c):
        return {n: (v.dtype.str, v.tolist()) if isinstance(v, np.ndarray)
                else v for n, v in c.items() if v is not None}
    return ((norm(cols), present.tolist()),
            (norm(want_cols), want_present.tolist()))


@pytest.mark.parametrize("method", _BATCH_METHODS)
def test_fid_index_batch_lookups_equal_the_dict(method):
    """Every batch path answers what the ``_rows`` dict answers, through
    the index's build, inserts, removes, row reuse, a rebuild and a batch
    below the crossover length: values, absent fids and dtypes alike."""
    rng = np.random.default_rng(14)
    cat = Catalog(n_shards=3)
    # sparse, non-sequential fids (Lustre FIDs are not 1..N)
    fids = rng.choice(1 << 40, size=3000, replace=False).astype(np.int64) + 1
    cat.upsert_batch([_entry(int(f), size=int(f) % 9973, owner=f"u{f % 7}")
                      for f in fids])
    live = set(fids.tolist())
    never = (rng.choice(1 << 40, size=300, replace=False) + (1 << 41)
             ).tolist()
    gone: list = []
    step = [0]

    def check(extra=()):
        step[0] += 1
        query = list(live) + gone + never + list(extra)
        rng.shuffle(query)
        got, want = _batch_vs_dict(cat, method, query, step[0])
        assert got == want, step[0]

    def built():                           # the catalog's builds counter
        return cat.shards[0].index_counters[2].value

    check()                                        # builds every index
    assert all(s._index is not None for s in cat.shards)
    b0 = built()
    stale = [s._index_struct for s in cat.shards]
    # inserts of new fids
    new = (rng.choice(1 << 40, size=30, replace=False) + (1 << 42)).tolist()
    cat.upsert_batch([_entry(f) for f in new])
    live |= set(new)
    check()
    # removes
    rm = rng.choice(sorted(live), size=30, replace=False).tolist()
    cat.remove_batch(rm)
    live -= set(rm)
    gone += rm
    check()
    # a re-insert that lands in its own freed row
    f0 = sorted(live)[5]
    shard, row0 = _by_dict(cat, f0)
    cat.remove(f0)
    cat.upsert(_entry(f0, size=7))
    assert _by_dict(cat, f0) == (shard, row0)
    check()
    # a removed fid whose old row now holds another fid
    f1 = sorted(live)[9]
    shard, row1 = _by_dict(cat, f1)
    other = next(f for f in range(3 << 40, (3 << 40) + 10)
                 if cat.shard_of(f) is shard)
    cat.remove(f1)
    cat.upsert(_entry(other, size=11))
    live.discard(f1)
    live.add(other)
    gone.append(f1)
    assert _by_dict(cat, other) == (shard, row1)
    check()
    # no rebuild so far: the misses above reached the dict
    assert [s._index_struct for s in cat.shards] == stale
    assert built() == b0
    # structural churn past the rebuild share rebuilds at the next lookup
    many = (rng.choice(1 << 40, size=400, replace=False) + (1 << 43)).tolist()
    cat.upsert_batch([_entry(f) for f in many])
    live |= set(many)
    rm = rng.choice(sorted(live), size=200, replace=False).tolist()
    cat.remove_batch(rm)
    live -= set(rm)
    gone += rm
    check()
    assert built() > b0
    assert all(s._index_struct == s._struct for s in cat.shards)
    # a batch below the crossover length takes the dict probe
    small = sorted(live)[:catalog_mod._INDEX_MIN_BATCH - 1] + gone[:1]
    got, want = _batch_vs_dict(cat, method, small, 99)
    assert got == want


class _Recorder:
    """Batch action that acts on nothing (the columnar act path)."""

    def __call__(self, e, params):
        return True

    @staticmethod
    def action_batch(batch, params):
        return [True] * len(batch)


def test_fid_index_counters_and_span_attribute():
    """Update-only churn keeps the builds flat and every fid on the index;
    inserted fids reach the dict until a rebuild; the act gather's span
    carries ``rows_dict``."""
    from repro.core import PolicyDefinition, PolicyEngine
    cat = Catalog(n_shards=2)
    fids = np.arange(5, 10005, 5, dtype=np.int64)     # 1,000 a shard
    cat.upsert_batch([_entry(int(f)) for f in fids])
    label = f'catalog="{cat._tlabels["catalog"]}"'
    idx = f'catalog_fid_index_rows{{{label},via="index"}}'
    dct = f'catalog_fid_index_rows{{{label},via="dict"}}'
    builds = f'catalog_fid_index_builds{{{label}}}'

    def counts():
        c = cat.telemetry.counter_values()
        return c[idx], c[dct], c[builds]

    cat.gather_rows(fids)
    assert counts() == (2000, 0, 2)
    for k in range(3):                            # update-only churn
        cat.update_fields_batch(fids[k::3].tolist(), size=k)
        cat.gather_rows(fids)
    assert counts() == (2000 + 3 * 2000 + 2000, 0, 2)

    def run(policy_fids):
        eng = PolicyEngine(cat, clock=lambda: 2e9)
        eng.register(PolicyDefinition.from_config(
            "p", action=_Recorder(), scope="type == file",
            evaluator="numpy", mutates=False, n_threads=1, batch_size=4096))
        rep = eng.run("p", matching="full")
        assert rep.matched == policy_fids
        tree = rep.telemetry["spans"]
        [act] = [c for c in tree["children"] if c["name"] == "run.act"]
        return [g["attrs"]["rows_dict"] for g in act["children"]
                if g["name"] == "run.act.gather"]

    assert sum(run(2000)) == 0
    new = list(range(10006, 10046))                # 20 a shard: under 1/16
    cat.upsert_batch([_entry(f) for f in new])
    i0, d0, b0 = counts()
    assert sum(run(2040)) == 40
    i1, d1, b1 = counts()
    assert (d1 - d0, i1 - i0, b1) == (40, 2000, b0)
    cat.remove_batch(fids[:300].tolist())          # 150 a shard: past it
    assert sum(run(1740)) == 0
    assert counts()[2] == b0 + 2


def test_fid_is_not_patchable():
    cat = Catalog(n_shards=2)
    cat.upsert(_entry(5))
    with pytest.raises(ValueError):
        cat.update_fields_batch([5], fid=6)
    assert cat.get(5).fid == 5 and cat.get(6) is None


def test_ambient_tally_adds_to_the_innermost_span():
    from repro.core import MetricRegistry
    from repro.core.telemetry import ambient_tally
    ambient_tally(rows_dict=3)                     # outside a trace: no-op
    reg = MetricRegistry()
    with reg.trace("outer") as outer:
        with reg.trace("inner", rows_dict=0) as inner:
            ambient_tally(rows_dict=2)
            ambient_tally(rows_dict=5)
        ambient_tally(other=1)
    assert inner.attrs["rows_dict"] == 7
    assert outer.attrs == {"other": 1}
