"""The operations traffic mixes are made of.

Each operation kind has four parts: ``make`` draws a request from the
traffic file's parameters, ``run`` serves it through the program's public
API, ``reference`` computes the answer in numpy over the benchmark's own
arrays (:mod:`bench.reference`), and ``compare`` returns
``(mismatches, volume_error)`` between a served answer and the reference.
``run`` and ``reference`` return answers in one normalized form, so the
control (the reference at a lower precision) compares the same way.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Tuple

import numpy as np

from . import reference as ref
from .data import CatalogState
from .deploy import request_policy

_FID = re.compile(r"f(\d+)$")


def dir_path(fmt: str, **given) -> str:
    """The directory of the path format down to the last component whose
    fields are all given: ``dir_path("/proj/{group}/{owner}/d{subdir}/f{fid}",
    group="grp3")`` is ``/proj/grp3``."""
    out = []
    for comp in fmt.strip("/").split("/"):
        fields = re.findall(r"\{(\w+)\}", comp)
        if any(f not in given for f in fields):
            break
        out.append(comp.format(**given))
    return "/" + "/".join(out)


def _index(name: str) -> int:
    return int(re.search(r"(\d+)$", name).group(1))


def user_pool(subject: dict, cat: dict) -> List[int]:
    """Owners whose entries a subject can see: its own, those of its
    groups (an owner's group is its index mod the group count), or the
    owners under its subtrees."""
    n, g = cat["owners"], cat["groups"]
    pool = {_index(o) for o in subject["owners"]}
    groups = {_index(x) for x in subject["groups"]}
    for p in subject["subtrees"]:
        users = re.findall(r"/user(\d+)", p)
        if users:
            pool.add(int(users[0]))
        else:
            groups |= {_index(x) for x in re.findall(r"grp\d+", p)}
    pool |= {o for o in range(n) if o % g in groups}
    return sorted(pool)


# -- make ---------------------------------------------------------------------

def make_du(rng, subject: dict, tcfg: dict, cat: dict) -> dict:
    fmt = cat["path"]
    if subject["subtrees"]:
        path = subject["subtrees"][int(rng.integers(len(subject["subtrees"])))]
    else:
        o = int(rng.choice(user_pool(subject, cat)))
        given = {"group": f"grp{o % cat['groups']}"}
        if rng.random() < 0.5:
            given["owner"] = f"user{o}"
        path = dir_path(fmt, **given)
    return {"op": "du", "path": path}


def make_find(rng, subject: dict, tcfg: dict, cat: dict) -> dict:
    tpls = tcfg["find_templates"]
    tpl = tpls[int(rng.integers(len(tpls)))]
    fill = {k: v[int(rng.integers(len(v)))] for k, v in tpl.items()
            if k != "criteria"}
    return {"op": "find", "criteria": tpl["criteria"].format(**fill)}


def make_top_files(rng, subject: dict, tcfg: dict, cat: dict) -> dict:
    return {"op": "top_files", "by": tcfg["top_files"]["by"],
            "k": tcfg["top_files"]["k"]}


def make_profile(rng, subject: dict, tcfg: dict, cat: dict) -> dict:
    p = tcfg["profile"]
    share = p["report_user"] / (p["report_user"] + p["top_users"])
    if rng.random() < share:
        user = int(rng.choice(user_pool(subject, cat)))
        return {"op": "report_user", "user": f"user{user}"}
    return {"op": "top_users", "k": p["top_users_k"]}


MAKERS: Dict[str, Callable] = {"du": make_du, "find": make_find,
                               "top_files": make_top_files,
                               "profile": make_profile}


# -- run ----------------------------------------------------------------------

def run_policy(dep, req: dict) -> dict:
    pol = request_policy(dep.cfg, req)
    rep = dep.engine.run(pol["name"], evaluator=pol["evaluator"],
                         matching=pol["matching"])
    fids, rules = dep.recorder.drain()
    fallback = int(rep.evaluator != pol["evaluator"]
                   or bool(rep.fallback_reason))
    return {"answer": (fids, rules), "fallback": fallback,
            "spans": rep.telemetry.get("spans")}


def run_du(dep, req: dict) -> dict:
    got = dep.reports.du(req["path"], subject=req["subject"]["name"])
    return {"answer": got}


def run_find(dep, req: dict) -> dict:
    paths = dep.reports.find(req["criteria"], subject=req["subject"]["name"])
    fids = np.fromiter((int(_FID.search(p).group(1)) for p in paths),
                       np.int64, count=len(paths))
    return {"answer": np.sort(fids)}


def run_top_files(dep, req: dict) -> dict:
    rows = dep.reports.top_files(by=req["by"], k=req["k"],
                                 subject=req["subject"]["name"])
    return {"answer": [(r["fid"], r[req["by"]]) for r in rows]}


def run_report_user(dep, req: dict) -> dict:
    rows = dep.cube.report_user(req["user"], subject=req["subject"]["name"])
    return {"answer": {r["type"]: {k: r[k] for k in
                                   ("count", "volume", "spc_used")}
                       for r in rows}}


def run_top_users(dep, req: dict) -> dict:
    rows = dep.cube.top_users(by="volume", k=req["k"],
                              subject=req["subject"]["name"])
    return {"answer": [{k: r[k] for k in ("user", "count", "volume")}
                       for r in rows]}


RUNNERS: Dict[str, Callable] = {
    "policy_run": run_policy, "du": run_du, "find": run_find,
    "top_files": run_top_files, "report_user": run_report_user,
    "top_users": run_top_users}


# -- reference and compare ------------------------------------------------------

def reference(st: CatalogState, cfg: dict, req: dict,
              precision: str = "f32"):
    """The reference answer of one request, in the normalized form."""
    op = req["op"]
    if op == "policy_run":
        return ref.plan(st, request_policy(cfg, req), precision)
    vis = ref.visible(st, req.get("subject"))
    if op == "du":
        return ref.du(st, vis, req["path"], precision)
    if op == "find":
        return ref.find(st, vis, req["criteria"], precision)
    if op == "top_files":
        vals = ref.top_values(st, vis, req["by"], req["k"], precision)
        if not vals.size:
            return []
        col = ref.at_precision(getattr(st, req["by"]).astype(np.float64),
                               precision)
        cand = np.nonzero(vis & ~st.is_dir & (col >= vals[-1]))[0]
        cand = cand[np.argsort(-col[cand], kind="stable")][: req["k"]]
        return [(int(st.fid[i]), float(col[i])) for i in cand]
    if op == "report_user":
        return ref.report_user(st, vis, req["user"], precision)
    if op == "top_users":
        vols = ref.user_volumes(st, vis, precision)
        ranked = sorted(vols.items(), key=lambda kv: -kv[1][1])[: req["k"]]
        return [{"user": u, "count": c, "volume": v}
                for u, (c, v) in ranked]
    raise ValueError(f"unknown op {op!r}")


def compare(st: CatalogState, req: dict, got, want,
            vol_limit: float) -> Tuple[int, float]:
    """(mismatches, worst relative volume error) of a served answer.

    Counts, fids, ranks and orders must be exact. Volume sums the device
    accumulates in f32 (du, the profile cube) are held to a relative
    error instead; ``vol_limit`` also bounds which near-equal users may
    swap places in a top-users list."""
    op = req["op"]
    if op == "policy_run":
        gf, gr = got
        wf, wr = want
        n = min(gf.size, wf.size)
        bad = int(np.count_nonzero(gf[:n] != wf[:n])
                  + np.count_nonzero(gr[:n] != wr[:n])
                  + abs(gf.size - wf.size))
        return bad, 0.0
    if op == "du":
        bad = int(got["count"] != want["count"]) \
            + int(got["files"] != want["files"])
        err = max(ref.rel_err(got["volume"], want["volume"]),
                  ref.rel_err(got["spc_used"], want["spc_used"]))
        return bad, err
    if op == "find":
        if got.size != want.size:
            return abs(got.size - want.size) + 1, 0.0
        return int(np.count_nonzero(got != want)), 0.0
    if op == "top_files":
        by = req["by"]
        col = getattr(st, by)
        vis = ref.visible(st, req.get("subject"))
        bad = abs(len(got) - len(want))
        fids = [f for f, _ in got]
        bad += len(fids) - len(set(fids))
        for (gf, gv), (_, wv) in zip(got, want):
            i = gf - 1
            ok = (0 <= i < st.n and vis[i] and not st.is_dir[i]
                  and float(col[i]) == gv and gv == wv)
            bad += int(not ok)
        return bad, 0.0
    if op == "report_user":
        bad = int(set(got) != set(want))
        err = 0.0
        for t in set(got) & set(want):
            bad += int(got[t]["count"] != want[t]["count"])
            err = max(err, ref.rel_err(got[t]["volume"], want[t]["volume"]),
                      ref.rel_err(got[t]["spc_used"], want[t]["spc_used"]))
        return bad, err
    if op == "top_users":
        vols = ref.user_volumes(st, ref.visible(st, req.get("subject")))
        return ref.top_users_gap(got, vols, req["k"], vol_limit)
    raise ValueError(f"unknown op {op!r}")
