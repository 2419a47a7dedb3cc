#!/usr/bin/env python3
"""Chip smoke test: the policy engine's main path, end to end, on a TPU.

One process, no children. In order:

1. checks that JAX's first device is a TPU (exits non-zero otherwise);
2. builds a catalog from ``--seed`` through ``Catalog.upsert_batch``
   (default 8,000,000 entries over 8 shards; heavy-tailed sizes and ages,
   every value f32-exact so the device's f32 columns hold it exactly);
3. uploads it into a device-resident ``DeviceColumnStore`` with the
   reports and profile-cube planes on, runs a 3-rule purge policy through
   ``PolicyEngine.run(evaluator="policy_scan_mesh")`` and checks the
   actioned fid sequence against ``evaluator="numpy"``, and the kernel's
   fused aggregates (count, volume, spc_used, size profile, per rule)
   against float64 host folds;
4. churns 1% of the entries with ``update_fields_batch`` (the scatter
   refresh), then creates and removes a few through
   ``commit_delta_batch`` (a structural re-upload), re-checking the policy
   against numpy after each;
5. serves ``find``, ``du``, ``top_files``, a grant-scoped ``find`` and a
   profile-cube report from the store and checks them against the host
   folds (fids, ranks and counts exactly; volume sums to rtol 1e-6).

Any fallback off the device path fails the run: an evaluator other than
``policy_scan_mesh``, a ``fallback_reason``, a report answered by a host
fold, or a compiled match without the Pallas kernel (``tpu_custom_call``).

    python chip_smoke.py                  # one chip, the whole path
    python chip_smoke.py --chips 4        # only the sharded store, 4 chips

``--chips 4`` runs only the sharded path — the policy run and find/du
over a 4-device mesh, compared with numpy — and prints each device's
``bytes_in_use``. Phase times and counts go to stdout as ``key=value``
lines; the last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# an f32-exact "now" (f32 spacing is 128 s here) and age cutoffs that are
# multiples of 128 s: the device's f32 thresholds equal the host's exactly
NOW = 1_750_000_000.0 - 1_750_000_000.0 % 128
SHARDS = 8                       # a multiple of 4, so --chips 4 splits them
N_OWNERS = 200
RULES = [("big", "size > 1GB", {}),
         ("cold", "last_access > 180d", {}),
         ("heavy_user", "owner == 'user3' and size > 16MB", {})]


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def f32_exact(x: np.ndarray) -> np.ndarray:
    """Round non-negative values to integers that f32 holds exactly."""
    return np.rint(x).astype(np.float32).astype(np.int64)


class CompileCounter:
    """Counts XLA compiles (cache fetches included) and their seconds,
    from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.count, self.seconds, self.cache_hits


def build_catalog(n: int, n_shards: int, seed: int):
    from repro.core import Catalog, Entry, FsType, HsmState
    rng = np.random.default_rng(seed)
    cat = Catalog(n_shards=n_shards)
    owners = [f"user{i}" for i in range(N_OWNERS)]
    chunk = 200_000
    for lo in range(0, n, chunk):
        k = min(chunk, n - lo)
        fid = np.arange(lo + 1, lo + k + 1)
        # sizes: log-normal around 64 KiB with a multi-GB tail
        size = f32_exact(np.minimum(
            rng.lognormal(math.log(64 << 10), 3.0, k), float(1 << 44)))
        blocks = f32_exact(np.ceil(size / 512.0))
        # ages: exponential with a 60-day mean, capped at 10 years
        age = np.minimum(rng.exponential(60 * 86400.0, k), 3650 * 86400.0)
        atime = (NOW - age).astype(np.float32).astype(np.float64)
        own = np.minimum(rng.zipf(1.3, k), N_OWNERS) - 1
        is_dir = rng.random(k) < 0.08
        hsm = rng.choice(5, size=k, p=[0.6, 0.1, 0.1, 0.15, 0.05])
        sub = rng.integers(0, 64, k)
        entries = [
            Entry(fid=f, name=f"f{f}", path=f"/fs/user{o}/d{d}/f{f}",
                  type=FsType.DIR if dr else FsType.FILE, size=s, blocks=b,
                  owner=owners[o], group=f"grp{o % 20}",
                  hsm_state=HsmState(h), atime=a, mtime=a, ctime=a)
            for f, s, b, a, o, dr, h, d in zip(
                fid.tolist(), size.tolist(), blocks.tolist(), atime.tolist(),
                own.tolist(), is_dir.tolist(), hsm.tolist(), sub.tolist())]
        cat.upsert_batch(entries)
    return cat


def same_report(a, b, path: str = "") -> None:
    """Exact equality, except volume sums (f32 partial sums on device),
    which agree to rtol 1e-6."""
    if isinstance(a, dict) and isinstance(b, dict):
        check(a.keys() == b.keys(), f"{path}: keys {a.keys()} != {b.keys()}")
        for k in a:
            if k in ("volume", "spc_used", "avg_size"):
                check(math.isclose(a[k], b[k], rel_tol=1e-6, abs_tol=0.5),
                      f"{path}.{k}: {a[k]} vs {b[k]}")
            else:
                same_report(a[k], b[k], f"{path}.{k}")
    elif isinstance(a, list) and isinstance(b, list):
        check(len(a) == len(b), f"{path}: {len(a)} vs {len(b)} rows")
        for i, (x, y) in enumerate(zip(a, b)):
            same_report(x, y, f"{path}[{i}]")
    else:
        check(a == b, f"{path}: {a!r} vs {b!r}")


def refresh(store) -> dict:
    """Refresh the store and wait until the device holds every block."""
    import jax
    stats = store.refresh()
    jax.block_until_ready([b for b in store._bufs if b is not None])
    return stats


class Recorder:
    """Policy action that records the actioned fid sequence."""

    def __init__(self) -> None:
        self.fids: list = []

    def __call__(self, entry, params) -> bool:
        self.fids.append(entry.fid)
        return True

    def action_batch(self, batch, params):
        self.fids.extend(batch.fids.tolist())
        return [True] * len(batch)

    def drain(self) -> list:
        out, self.fids = self.fids, []
        return out


def check_agg(eng, tag: str) -> None:
    """The kernel's fused aggregates (``store.match`` with its default
    ``with_agg=True``) against float64 host folds of the same programs.

    Counts and the size profile are exact. Volume and spc_used are f32
    sums accumulated tile by tile on each device: the recursive-summation
    bound for non-negative terms is (chain length) * 2**-24 relative, the
    chain being the tiles per device, the in-tile reduction and the psum."""
    from repro.core.profiles import size_buckets_np
    store, cat = eng.device_store, eng.catalog
    programs = eng._programs(eng.policies["purge"], None)
    agg = store.match(programs, NOW).agg
    cols = cat.arrays()
    size = np.asarray(cols["size"], np.int64)
    blocks = np.asarray(cols["blocks"], np.int64)
    masks = [p.mask(cols, cat.strings, NOW) for p in programs]
    rtol = ((store._rp // store.tile) + math.log2(store.tile)
            + store.n_devices) * 2.0 ** -24
    want = {"count": [int(m.sum()) for m in masks],
            "volume": [int(size[m].sum()) for m in masks],
            "spc_used": [int(blocks[m].sum()) for m in masks]}
    got = {k: [agg[k]] + agg[f"rule_{k}"] for k in want}
    check(got["count"] == want["count"],
          f"{tag}: agg counts {got['count']} vs host {want['count']}")
    worst = 0.0
    for k in ("volume", "spc_used"):
        for g, w in zip(got[k], want[k]):
            err = abs(g - w) / max(w, 1)
            check(err <= rtol, f"{tag}: agg {k} {g} vs host {w} "
                               f"(rel {err:.3g} > {rtol:.3g})")
            worst = max(worst, err)
    hist = np.bincount(size_buckets_np(size[masks[0]]), minlength=10)
    check(agg["size_profile"] == hist.tolist(),
          f"{tag}: size profile {agg['size_profile']} vs {hist.tolist()}")
    check(agg["any_match"] == (want["count"][0] > 0),
          f"{tag}: any_match {agg['any_match']}")
    say(agg=tag, count=int(agg["count"]), volume_rel_err=worst,
        rtol_bound=rtol)


def policy_round(eng, act: Recorder, tag: str, counter: CompileCounter):
    """One mesh run checked against a numpy run of the same state."""
    c0 = counter.snapshot()
    t0 = time.perf_counter()
    r_mesh = eng.run("purge", evaluator="policy_scan_mesh")
    dt = time.perf_counter() - t0
    c1 = counter.snapshot()
    seq_mesh = act.drain()
    check(r_mesh.evaluator == "policy_scan_mesh",
          f"{tag}: evaluator {r_mesh.evaluator} ({r_mesh.fallback_reason})")
    check(not r_mesh.fallback_reason,
          f"{tag}: fallback {r_mesh.fallback_reason}")
    t0 = time.perf_counter()
    r_np = eng.run("purge", evaluator="numpy")
    dt_np = time.perf_counter() - t0
    seq_np = act.drain()
    check(r_mesh.matched == r_np.matched,
          f"{tag}: matched {r_mesh.matched} vs numpy {r_np.matched}")
    check(seq_mesh == seq_np, f"{tag}: actioned sequences differ")
    check_agg(eng, tag)
    say(phase=tag, run_s=dt, numpy_run_s=dt_np, matched=r_mesh.matched,
        actioned=len(seq_mesh), compiles=c1[0] - c0[0],
        compile_s=c1[1] - c0[1], cache_hits=c1[2] - c0[2],
        evaluator=r_mesh.evaluator)
    return c1[0] - c0[0], c1[1] - c0[1]


def run(args) -> dict:
    import jax
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is {dev.platform} ({dev})")
    devices = jax.devices()
    check(len(devices) >= args.chips,
          f"--chips {args.chips} but JAX sees {len(devices)} devices")

    from repro.launch.compile_cache import enable_compile_cache
    say(compile_cache=enable_compile_cache())
    counter = CompileCounter()

    from repro.core import (DeviceColumnStore, Entry, FsType, GrantTable,
                            PolicyDefinition, PolicyEngine)
    from repro.core.profiles import ProfileCube
    from repro.core.reports import Reports
    from repro.launch.mesh import make_shards_mesh

    t0 = time.perf_counter()
    cat = build_catalog(args.entries, SHARDS, args.seed)
    say(phase="build", entries=len(cat), shards=SHARDS,
        seconds=time.perf_counter() - t0)

    clock = lambda: NOW                                  # noqa: E731
    store = DeviceColumnStore(cat, make_shards_mesh(args.chips))
    full = args.chips == 1
    grants = GrantTable()
    grants.add_subject("user3")
    grants.add_subject("proj-aud", owners=(), subtrees=("/fs/user5",))
    r_store = Reports(cat, clock=clock).attach_device_store(store)
    r_host = Reports(cat, clock=clock)
    if full:
        r_store.attach_grants(grants)
        r_host.attach_grants(grants)
        pc_store = ProfileCube(cat, clock=clock).attach_device_store(store)

    t0 = time.perf_counter()
    stats = refresh(store)
    say(phase="cold_upload", seconds=time.perf_counter() - t0,
        groups_uploaded=stats["full"], devices=store.n_devices)

    act = Recorder()
    eng = PolicyEngine(cat, clock=clock)
    eng.register(PolicyDefinition.from_config(
        name="purge", action=act, scope="type == file", rules=RULES,
        sort_by="atime", mutates=False, batch_size=4096))
    eng.attach_device_store(store)

    n_comp, comp_s = policy_round(eng, act, "first_call", counter)
    say(phase="first_call_compile", compiles=n_comp, seconds=comp_s)
    n_warm, _ = policy_round(eng, act, "warm_run", counter)
    check(n_warm == 0, f"warm run compiled {n_warm} programs")

    text = store.compiled_match_text(
        eng._programs(eng.policies["purge"], None), NOW)
    check("tpu_custom_call" in text, "compiled match has no Pallas kernel")
    say(match_kernel="tpu_custom_call")

    rng = np.random.default_rng(args.seed + 1)
    if full:
        # 1% churn as pure updates: the delta-scatter refresh
        churn = rng.choice(np.arange(1, args.entries + 1),
                           size=max(1, args.entries // 100), replace=False)
        parts = np.array_split(churn, 3)
        cat.update_fields_batch(parts[0].tolist(), atime=NOW)
        cat.update_fields_batch(parts[1].tolist(), size=2 << 30)
        cat.update_fields_batch(parts[2].tolist(), atime=NOW - 400 * 86400.0,
                                size=4096, blocks=8)
        before = store.delta_refreshes
        t0 = time.perf_counter()
        stats = refresh(store)
        say(phase="scatter_refresh", seconds=time.perf_counter() - t0,
            rows=churn.size, full=stats["full"])
        check(stats["full"] == 0 and store.delta_refreshes > before,
              f"1% update churn did not take the scatter path: {stats}")
        policy_round(eng, act, "after_scatter", counter)

    # a few creates and removes: a structural re-upload
    new = [Entry(fid=args.entries + i + 1, name=f"n{i}",
                 path=f"/fs/user3/new/n{i}", type=FsType.FILE,
                 size=(i + 1) << 30, blocks=(i + 1) << 21, owner="user3",
                 group="grp3", atime=NOW - 200 * 86400.0)
           for i in range(16)]
    gone = rng.choice(np.arange(1, args.entries + 1), size=16,
                      replace=False).tolist()
    cat.commit_delta_batch(new, gone)
    uploads = store.full_uploads
    t0 = time.perf_counter()
    stats = refresh(store)
    say(phase="structural_refresh", seconds=time.perf_counter() - t0,
        created=len(new), removed=len(gone), full=stats["full"])
    check(store.full_uploads > uploads, "creates/removes did not re-upload")
    policy_round(eng, act, "after_structural", counter)

    # reports: store-served vs host folds
    host0 = r_store.host_served
    queries0 = store.store_queries
    t0 = time.perf_counter()
    checks = [("find", lambda r: r.find("size > 4GB and type == file")),
              ("du_user", lambda r: r.du("/fs/user3")),
              ("du_dir", lambda r: r.du("/fs/user0/d7"))]
    if full:
        checks += [
            ("top_files", lambda r: r.top_files(by="size", k=100)),
            ("find_scoped", lambda r: r.find("size > 1GB",
                                             subject="proj-aud"))]
    for name, query in checks:
        t1 = time.perf_counter()
        got = query(r_store)
        t2 = time.perf_counter()
        want = query(r_host)
        t3 = time.perf_counter()
        same_report(got, want, name)
        check(r_store.last_fallback_reason is None,
              f"{name}: {r_store.last_fallback_reason}")
        say(report=name, rows=len(got), store_s=t2 - t1, host_s=t3 - t2)
    if full:
        t1 = time.perf_counter()
        got = (pc_store.report_user("user3", NOW),
               pc_store.top_users("volume", 10, now=NOW))
        t2 = time.perf_counter()
        oracle = ProfileCube(cat, clock=clock)
        oracle.rebuild(now=NOW)
        want = (oracle.report_user("user3", NOW),
                oracle.top_users("volume", 10, now=NOW))
        t3 = time.perf_counter()
        same_report(list(got), list(want), "profile_cube")
        say(report="profile_cube", groups=len(pc_store.groups),
            store_s=t2 - t1, host_s=t3 - t2)
    check(r_store.host_served == host0,
          f"{r_store.host_served - host0} reports answered by host folds")
    check(store.store_queries > queries0, "no report was store-served")
    say(phase="reports", seconds=time.perf_counter() - t0)

    count, seconds, hits = counter.snapshot()
    say(phase="all", compiles=count, compile_s=seconds, cache_hits=hits)
    for d in devices[:args.chips]:
        m = d.memory_stats() or {}
        say(device=d.id, bytes_in_use=m.get("bytes_in_use"),
            peak_bytes_in_use=m.get("peak_bytes_in_use"))
        check(m.get("bytes_in_use", 0) > 0, f"device {d.id} holds nothing")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": args.chips}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--entries", type=int, default=8_000_000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    device = run(args)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
