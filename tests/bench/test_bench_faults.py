"""With the timed path broken underneath, ``correct`` comes out false:
once for each fault a one-chip cell can have (a one-chip cell has no
exchange between chips to leave out)."""
import numpy as np
import pytest

from bench_helpers import no_cache, tiny_run  # noqa: F401

CELLS = ["scratch_purge.churn1", "project_reports.scoped_steady"]


def _state_unchanged(monkeypatch):
    """A refresh that drops the churned rows: the device keeps its state."""
    from repro.core.device_store import DeviceColumnStore

    def stale(self, group):
        group.dirty = set()
        group.versions = self._shard_versions(group)
        return True
    monkeypatch.setattr(DeviceColumnStore, "_delta_refresh", stale)


def _half_left_out(monkeypatch):
    """A match and a top-N listing that lose half of their rows."""
    from repro.core.device_store import DeviceColumnStore
    match, top = DeviceColumnStore._match_locked, DeviceColumnStore.top_files

    def half_match(self, *a, **k):
        m = match(self, *a, **k)
        m._group_idx = [ix[: ix.size // 2] for ix in m._group_idx]
        m._group_rule = [r[: r.size // 2] for r in m._group_rule]
        return m

    def half_top(self, *a, **k):
        rows = top(self, *a, **k)
        return rows[: len(rows) // 2]
    monkeypatch.setattr(DeviceColumnStore, "_match_locked", half_match)
    monkeypatch.setattr(DeviceColumnStore, "top_files", half_top)


def _answer_altered(monkeypatch):
    """Two fids of each plan swapped and one count of each du altered."""
    from repro.core.device_store import DeviceColumnStore, MeshMatch
    plan, du = MeshMatch.plan, DeviceColumnStore.du

    def plan_altered(self, sort_by):
        fids, sizes, keys, rules = plan(self, sort_by)
        # the first fid trades places with one of another sort key: two
        # rows of one key (churned to the same atime) would plan alike
        other = np.flatnonzero(keys != keys[0]) if fids.size else []
        if len(other):
            j = other[0]
            fids = fids.copy()
            fids[0], fids[j] = fids[j], fids[0]
        return fids, sizes, keys, rules

    def du_altered(self, *a, **k):
        out = du(self, *a, **k)
        return dict(out, count=out["count"] + 1)
    monkeypatch.setattr(MeshMatch, "plan", plan_altered)
    monkeypatch.setattr(DeviceColumnStore, "du", du_altered)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_caught(no_cache, monkeypatch, workload, fault):
    FAULTS[fault](monkeypatch)
    out = tiny_run(workload)
    assert not out.result["correct"], out.checks
