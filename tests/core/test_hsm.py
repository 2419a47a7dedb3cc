"""HSM archive/release state machine + OST watermark purge (C7/C8)."""
import pytest

from repro.core import (Catalog, HsmCoordinator, HsmState, PolicyEngine,
                        Scanner)
from repro.fs import HsmBackend, LustreSim


def _setup(n_files=20, fsize=1000, ost_capacity=8000, n_osts=2,
           clock=None):
    kw = dict(clock=clock) if clock else {}
    fs = LustreSim(n_osts=n_osts, ost_capacity=ost_capacity,
                   hsm=HsmBackend(), **kw)
    d = fs.mkdir(fs.root_fid(), "data")
    fids = []
    for i in range(n_files):
        f = fs.create(d, f"f{i}", owner="u")
        fs.write(f, fsize)
        fids.append(f)
    cat = Catalog()
    Scanner(fs, cat).scan()
    eng = PolicyEngine(cat, clock=clock) if clock else PolicyEngine(cat)
    return fs, d, fids, cat, eng


def test_archive_then_release_frees_ost_space(fake_clock):
    fs, d, fids, cat, eng = _setup(clock=fake_clock)
    coord = HsmCoordinator(fs, cat, eng, high_wm=50.0, low_wm=20.0)
    rep = coord.archive_pass()
    assert rep.succeeded == 20 and rep.failed == 0
    assert fs.hsm.count() == 20
    used_before = sum(o.used for o in fs.osts)
    fake_clock.advance(100)
    reports = coord.space_check()        # OSTs above 50% -> purge to 20%
    assert reports, "watermark should have fired"
    used_after = sum(o.used for o in fs.osts)
    assert used_after < used_before
    for o in fs.osts:
        assert o.usage_pct <= 50.0
    # released entries are stubs: size kept, blocks 0
    released = [f for f in fids
                if cat.get(f) and cat.get(f).hsm_state == HsmState.RELEASED]
    assert released
    e = cat.get(released[0])
    assert e.size == 1000 and e.blocks == 0


def test_read_restores_released_file(fake_clock):
    fs, d, fids, cat, eng = _setup(clock=fake_clock)
    coord = HsmCoordinator(fs, cat, eng)
    coord.archive_pass()
    fs.hsm_release(fids[0])
    assert fs.stat(fids[0]).hsm_state == HsmState.RELEASED
    size = fs.read(fids[0])              # transparent restore
    assert size == 1000
    assert fs.stat(fids[0]).hsm_state == HsmState.ARCHIVED
    assert fs.stat(fids[0]).blocks == 1000


def test_dirty_after_write_requires_rearchive(fake_clock):
    fs, d, fids, cat, eng = _setup(clock=fake_clock)
    coord = HsmCoordinator(fs, cat, eng)
    coord.archive_pass()
    fs.write(fids[1], 50)
    assert fs.stat(fids[1]).hsm_state == HsmState.DIRTY
    with pytest.raises(RuntimeError):
        fs.hsm_release(fids[1])          # cannot release a dirty file


def test_undelete(fake_clock):
    fs, d, fids, cat, eng = _setup(clock=fake_clock)
    coord = HsmCoordinator(fs, cat, eng)
    coord.archive_pass()
    victim = fids[2]
    fs.unlink(victim)
    assert fs.stat(victim) is None
    new_fid = coord.undelete(victim, d, "f2_restored")
    assert new_fid is not None
    assert fs.stat(new_fid).size == 1000


def test_disaster_recovery_rebuild(fake_clock):
    fs, d, fids, cat, eng = _setup(clock=fake_clock)
    # catalog lost: rebuild by scan
    cat2 = Catalog()
    eng2 = PolicyEngine(cat2, clock=fake_clock)
    coord = HsmCoordinator(fs, cat2, eng2)
    n = coord.rebuild_catalog()
    assert n == fs.count()
    assert len(cat2) == fs.count()


class _Clock:
    def __init__(self):
        self.t = 1_000_000.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _hsm_cycle(store):
    """An archive pass then a watermark release on a small file system,
    matched with the catalog on a device store or not: the fs calls in
    order, every file's resulting HSM state, and the run reports."""
    from repro.core import DeviceColumnStore
    from repro.launch.mesh import make_shards_mesh
    clock = _Clock()
    fs = LustreSim(n_osts=4, ost_capacity=60_000, hsm=HsmBackend(),
                   clock=clock)
    d = fs.mkdir(fs.root_fid(), "data")
    fids = []
    for i in range(40):
        clock.advance(3)
        f = fs.create(d, f"f{i}", owner="u")
        fs.write(f, 1000 * (i % 7 + 1))
        fids.append(f)
    for f in fids[::-3]:                 # LRU order differs from mtime order
        clock.advance(2)
        fs.read(f)
    cat = Catalog(n_shards=4)
    Scanner(fs, cat).scan()
    eng = PolicyEngine(cat, clock=clock)
    if store:
        eng.attach_device_store(DeviceColumnStore(cat, make_shards_mesh()))
    calls = []
    for op in ("hsm_archive", "hsm_release"):
        def logged(fid, *a, _op=op, _fn=getattr(fs, op), **kw):
            calls.append((_op, fid))
            return _fn(fid, *a, **kw)
        setattr(fs, op, logged)
    coord = HsmCoordinator(fs, cat, eng, archive_age="40s", high_wm=50.0,
                           low_wm=20.0)
    reports = [coord.archive_pass()]
    clock.advance(100)
    fs.write(fids[0], 500)               # archived, then dirty again
    cat.update_fields(fids[0], hsm_state=HsmState.DIRTY)
    reports += coord.space_check()
    states = {f: (cat.get(f).hsm_state, fs.stat(f).hsm_state) for f in fids}
    return calls, states, reports


def test_store_attached_coordinator_matches_on_the_device():
    host = _hsm_cycle(store=False)
    dev = _hsm_cycle(store=True)
    calls, states, reports = dev
    assert [r.evaluator for r in host[2]] == ["numpy"] * len(host[2])
    assert [r.evaluator for r in reports] == \
        ["policy_scan_mesh"] * len(reports)
    assert all(r.fallback_reason == "" for r in host[2] + reports)
    # an archive pass, then a release per OST over its high watermark
    assert len(reports) > 2 and all(r.trigger.startswith("watermark:")
                                     for r in reports[1:])
    assert {op for op, _ in calls} == {"hsm_archive", "hsm_release"}
    assert 0 < reports[0].succeeded < 40    # the newest files are too young
    assert calls == host[0]
    assert states == host[1]
    assert [(r.matched, r.succeeded, r.volume) for r in reports] == \
        [(r.matched, r.succeeded, r.volume) for r in host[2]]
