"""The system under test, built from a configuration and the seeded state.

This is the one module that calls the program's set-up API: it loads the
benchmark's arrays into a ``Catalog`` through ``upsert_batch``, puts the
catalog on the device in a ``DeviceColumnStore``, turns on the planes the
configuration names, and registers the configuration's policies and
subjects.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from .data import CHUNK, CatalogState, owners_by_share


class Recorder:
    """Policy action that records each call: the fids and the rule."""

    needs_entries = False

    def __init__(self) -> None:
        self.calls: List[tuple] = []

    def __call__(self, entry, params) -> bool:
        self.calls.append((np.asarray([entry.fid], np.int64),
                           params.get("rule", -1)))
        return True

    def action_batch(self, batch, params):
        self.calls.append((np.asarray(batch.fids, np.int64).copy(),
                           params.get("rule", -1)))
        return [True] * len(batch)

    def drain(self):
        """(fids, rule index) of every call since the last drain."""
        calls, self.calls = self.calls, []
        if not calls:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        return (np.concatenate([f for f, _ in calls]),
                np.concatenate([np.full(f.size, r, np.int64)
                                for f, r in calls]))


def policies(cfg: dict) -> Dict[str, dict]:
    """The configuration's policies by name: its ``policies`` list, or its
    one ``policy`` (none where that is null or absent)."""
    if "policy" in cfg and "policies" in cfg:
        raise ValueError("a configuration holds 'policy' or 'policies', "
                         "not both")
    pols = cfg.get("policies")
    if pols is None:
        pols = [cfg["policy"]] if cfg.get("policy") else []
    out: Dict[str, dict] = {}
    for pol in pols:
        if pol["name"] in out:
            raise ValueError(f"two policies are named {pol['name']!r}")
        out[pol["name"]] = pol
    return out


def request_policy(cfg: dict, req: dict) -> dict:
    """The policy a ``policy_run`` request runs: the one it names, or the
    configuration's only policy where it names none."""
    pols = policies(cfg)
    name = req.get("policy")
    if name is None:
        if len(pols) != 1:
            raise ValueError(f"a policy_run names no policy, and the "
                             f"configuration has {len(pols)}")
        return next(iter(pols.values()))
    if name not in pols:
        raise ValueError(f"unknown policy {name!r}; known: "
                         f"{', '.join(sorted(pols))}")
    return pols[name]


def subjects(cfg: dict) -> List[dict]:
    """The configuration's subjects, most active first: own-files users
    (the owners with the most entries), group auditors, subtree auditors,
    service accounts. Each is {name, owners, groups, subtrees}."""
    spec = cfg.get("subjects") or {}
    cat = cfg["catalog"]
    out = []
    own = spec.get("own_files")
    if own:
        for o in owners_by_share(cat)[: own["count"]]:
            out.append({"name": f"user{o}", "owners": [f"user{o}"],
                        "groups": [], "subtrees": []})
    for g in spec.get("group_auditors", []):
        out.append({"name": f"aud-{g}", "owners": [], "groups": [g],
                    "subtrees": []})
    for i, p in enumerate(spec.get("subtree_auditors", [])):
        out.append({"name": f"aud-sub{i}", "owners": [], "groups": [],
                    "subtrees": [p]})
    for name, scope in spec.get("service_accounts", {}).items():
        if scope != "all_groups":
            raise ValueError(f"unknown service account scope {scope!r}")
        out.append({"name": name, "owners": [],
                    "groups": [f"grp{g}" for g in range(cat["groups"])],
                    "subtrees": []})
    return out


@dataclasses.dataclass
class Deployment:
    cfg: dict
    catalog: object
    store: object
    engine: Optional[object]
    recorder: Optional[Recorder]
    reports: Optional[object]
    cube: Optional[object]
    subjects: List[dict]


def load_catalog(st: CatalogState, n_shards: int):
    from repro.core import Catalog, Entry, FsType, HsmState
    cat = Catalog(n_shards=n_shards)
    types = (FsType.FILE, FsType.DIR)
    hsm = list(HsmState)
    for lo in range(0, st.n, CHUNK):
        hi = min(lo + CHUNK, st.n)
        cat.upsert_batch([
            Entry(fid=f, name=f"f{f}", path=st.path(i),
                  type=types[d], size=s, blocks=b, owner=f"user{o}",
                  group=f"grp{g}", hsm_state=hsm[h], atime=a, mtime=m,
                  ctime=a)
            for i, f, d, s, b, o, g, h, a, m in zip(
                range(lo, hi), st.fid[lo:hi].tolist(),
                st.is_dir[lo:hi].astype(int).tolist(),
                st.size[lo:hi].tolist(), st.blocks[lo:hi].tolist(),
                st.owner[lo:hi].tolist(), st.group[lo:hi].tolist(),
                st.hsm[lo:hi].tolist(), st.atime[lo:hi].tolist(),
                st.mtime[lo:hi].tolist())])
    return cat


def build(cfg: dict, st: CatalogState, n_devices: int) -> Deployment:
    """Catalog, device store, planes, policies and subjects; starts the
    catalog's upload to the device (the warm-up waits for it)."""
    from repro.core import DeviceColumnStore, GrantTable, PolicyDefinition, \
        PolicyEngine
    from repro.core.profiles import ProfileCube
    from repro.core.reports import Reports
    from repro.launch.mesh import make_shards_mesh

    now = st.now
    clock = lambda: now                                   # noqa: E731
    t0 = time.perf_counter()
    cat = load_catalog(st, cfg["catalog"]["shards"])
    t_load = time.perf_counter() - t0
    store = DeviceColumnStore(cat, make_shards_mesh(n_devices))
    planes = set(cfg.get("planes", []))
    unknown = planes - {"reports", "cube", "permissions"}
    if unknown:
        raise ValueError(f"unknown planes {sorted(unknown)}")
    subs = subjects(cfg)
    grants = None
    if "permissions" in planes:
        grants = GrantTable()
        for s in subs:
            grants.add_subject(s["name"], owners=s["owners"],
                               groups=s["groups"], subtrees=s["subtrees"])
    reports = cube = None
    if "reports" in planes:
        reports = Reports(cat, clock=clock).attach_device_store(store)
        if grants is not None:
            reports.attach_grants(grants)
    if "cube" in planes:
        cube = ProfileCube(cat, clock=clock).attach_device_store(store)
        if grants is not None:
            cube.attach_grants(grants)
    engine = recorder = None
    pols = policies(cfg)
    if pols:
        # one recorder serves every policy: runs are sequential and each
        # run drains it
        recorder = Recorder()
        engine = PolicyEngine(cat, clock=clock)
        for pol in pols.values():
            engine.register(PolicyDefinition.from_config(
                name=pol["name"], action=recorder, scope=pol["scope"],
                rules=[(name, cond, {"rule": r})
                       for r, (name, cond) in enumerate(pol["rules"])],
                sort_by=pol["sort_by"], mutates=pol["mutates"],
                batch_size=pol["batch_size"]))
        engine.attach_device_store(store)
    t0 = time.perf_counter()
    store.refresh()
    print(f"info setup_catalog_load_s={t_load} "
          f"setup_upload_s={time.perf_counter() - t0}", file=sys.stderr,
          flush=True)
    return Deployment(cfg, cat, store, engine, recorder, reports, cube, subs)
