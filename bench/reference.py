"""The plain reference: what each served answer should be, in numpy over
the benchmark's own arrays (:class:`bench.data.CatalogState`).

It imports nothing of the program and takes nothing the program made. It
has its own small parser for the criteria strings the configurations and
traffic use (``and``/``or``/``not``, parentheses, comparisons of size,
blocks, ages, type, owner, group and HSM state), and reads paths by
their components, as the path format of the configuration lays them out.

``precision="bf16"`` is the control: every size, block count, time and
threshold is rounded to bfloat16 first and the rest computed exactly, the
least lossy reading one precision below the f32 the store keeps.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .data import CatalogState

_UNITS = {"K": 1 << 10, "M": 1 << 20, "G": 1 << 30, "T": 1 << 40,
          "P": 1 << 50}
_DURATIONS = (("min", 60), ("sec", 1), ("s", 1), ("m", 60), ("h", 3600),
              ("d", 86400), ("w", 7 * 86400), ("y", 365 * 86400))
_AGE = {"last_access": "atime", "last_mod": "mtime"}
# Lustre-HSM states by the code ``bench/data.py`` draws for each
# (``hsm_state_p`` gives their shares in this order)
_HSM = ("none", "dirty", "archiving", "archived", "released", "restoring",
        "lost")
_TOKEN = re.compile(r"\s*(?:(\()|(\))|(==|!=|>=|<=|>|<)|'([^']*)'|([\w.]+))")


def at_precision(x: np.ndarray, precision: str) -> np.ndarray:
    """``x`` as the given precision holds it ("f32": unchanged, since the
    benchmark's values are f32-exact)."""
    if precision == "f32":
        return x
    if precision == "bf16":
        import ml_dtypes
        return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16) \
            .astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


# -- criteria ----------------------------------------------------------------

def _tokens(text: str) -> List[Tuple[str, str]]:
    out, pos = [], 0
    text = text.strip()
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if not m or m.end() == pos:
            raise ValueError(f"cannot parse {text[pos:]!r}")
        pos = m.end()
        lp, rp, op, s, w = m.groups()
        out.append(("(", lp) if lp else (")", rp) if rp else
                   ("op", op) if op else ("str", s) if s is not None
                   else ("word", w))
    return out


def _value(attr: str, tok: str) -> object:
    if attr in _AGE:
        t = tok.lower()
        for suffix, mult in _DURATIONS:
            if t.endswith(suffix):
                return float(t[: -len(suffix)]) * mult
        return float(t)
    if attr in ("size", "blocks"):
        t = tok.upper().rstrip("B")
        if t and t[-1] in _UNITS:
            return int(float(t[:-1]) * _UNITS[t[-1]])
        return int(float(t))
    return tok


def parse(text: str):
    """Criteria string -> nested tuples: ("and"|"or", a, b), ("not", a),
    ("cmp", attr, op, value)."""
    toks = _tokens(text)
    pos = [0]

    def peek():
        return toks[pos[0]] if pos[0] < len(toks) else (None, None)

    def take():
        t = peek()
        pos[0] += 1
        return t

    def or_():
        e = and_()
        while peek() == ("word", "or"):
            take()
            e = ("or", e, and_())
        return e

    def and_():
        e = not_()
        while peek() == ("word", "and"):
            take()
            e = ("and", e, not_())
        return e

    def not_():
        if peek() == ("word", "not"):
            take()
            return ("not", not_())
        if peek()[0] == "(":
            take()
            e = or_()
            if take()[0] != ")":
                raise ValueError(f"missing ')' in {text!r}")
            return e
        _, attr = take()
        kind, op = take()
        if kind != "op":
            raise ValueError(f"expected an operator in {text!r}")
        _, tok = take()
        return ("cmp", attr, op, _value(attr, tok))

    e = or_()
    if pos[0] != len(toks):
        raise ValueError(f"trailing tokens in {text!r}")
    return e


def _compare(lhs: np.ndarray, op: str, rhs) -> np.ndarray:
    return {"==": lhs == rhs, "!=": lhs != rhs, ">": lhs > rhs,
            ">=": lhs >= rhs, "<": lhs < rhs, "<=": lhs <= rhs}[op]


def _index(name: str, prefix: str) -> int:
    m = re.fullmatch(re.escape(prefix) + r"(\d+)", name)
    return int(m.group(1)) if m else -1


def mask(expr, st: CatalogState, precision: str = "f32") -> np.ndarray:
    """Boolean row mask of a parsed criteria expression."""
    kind = expr[0]
    if kind == "and":
        return mask(expr[1], st, precision) & mask(expr[2], st, precision)
    if kind == "or":
        return mask(expr[1], st, precision) | mask(expr[2], st, precision)
    if kind == "not":
        return ~mask(expr[1], st, precision)
    _, attr, op, value = expr
    if attr in ("size", "blocks"):
        col = at_precision(getattr(st, attr).astype(np.float64), precision)
        cut = at_precision(np.asarray([value], np.float64), precision)
        return _compare(col, op, float(cut[0]))
    if attr in _AGE:
        # age > T  <=>  time < now - T, at the column's precision
        col = at_precision(getattr(st, _AGE[attr]), precision)
        cut = float(at_precision(np.asarray([st.now - value]), precision)[0])
        flip = {">": "<", ">=": "<=", "<": ">", "<=": ">=", "==": "==",
                "!=": "!="}[op]
        return _compare(col, flip, cut)
    if attr == "type":
        want = {"file": False, "dir": True, "directory": True}[value]
        hit = st.is_dir == want
        return hit if op == "==" else ~hit
    if attr in ("owner", "group"):
        code = _index(value, "user" if attr == "owner" else "grp")
        hit = getattr(st, attr) == code
        return hit if op == "==" else ~hit
    if attr == "hsm_state":
        if op not in ("==", "!=") or value.lower() not in _HSM:
            raise ValueError(f"the reference has no hsm_state {op} {value}")
        hit = st.hsm == _HSM.index(value.lower())
        return hit if op == "==" else ~hit
    raise ValueError(f"the reference has no attribute {attr!r}")


# -- paths and visibility -----------------------------------------------------

def subtree(st: CatalogState, prefix: str) -> np.ndarray:
    """Rows at or under ``prefix``, matched component by component
    against the configuration's path format."""
    fmt = st.path_fmt.strip("/").split("/")
    parts = prefix.strip("/").split("/") if prefix.strip("/") else []
    hit = np.ones(st.n, bool)
    if len(parts) > len(fmt):
        return np.zeros(st.n, bool)
    cols = {"{owner}": (st.owner, "user"), "{group}": (st.group, "grp")}
    for want, f in zip(parts, fmt):
        if f in cols:
            col, name = cols[f]
            hit &= col == _index(want, name)
            continue
        m = re.fullmatch(r"(\w*)\{(subdir|fid)\}", f)
        if m:
            col = st.subdir if m.group(2) == "subdir" else st.fid
            hit &= col == _index(want, m.group(1))
        elif want != f:
            return np.zeros(st.n, bool)
    return hit


def visible(st: CatalogState, subject: Optional[dict]) -> np.ndarray:
    """Rows a subject may see: its owners, its groups and its subtrees."""
    if subject is None:
        return np.ones(st.n, bool)
    vis = np.zeros(st.n, bool)
    owners = [_index(o, "user") for o in subject.get("owners", ())]
    groups = [_index(g, "grp") for g in subject.get("groups", ())]
    if owners:
        vis |= np.isin(st.owner, owners)
    if groups:
        vis |= np.isin(st.group, groups)
    for pref in subject.get("subtrees", ()):
        vis |= subtree(st, pref)
    return vis


# -- answers ---------------------------------------------------------------

def plan(st: CatalogState, policy: dict, precision: str = "f32"
         ) -> Tuple[np.ndarray, np.ndarray]:
    """The actioned sequence of one policy run: (fids, rule index).

    Matched files sorted by ``sort_by`` with the fid as tie-break, cut
    into chunks of ``batch_size``; within a chunk the action is called
    once per rule, in rule order, with that rule's rows in plan order."""
    scope = mask(parse(policy["scope"]), st, precision)
    rules = [mask(parse(cond), st, precision) for _, cond in
             policy["rules"]]
    hit = scope & np.logical_or.reduce(rules)
    rule = np.full(st.n, -1, np.int64)
    for r in range(len(rules) - 1, -1, -1):
        rule[rules[r]] = r
    idx = np.nonzero(hit)[0]
    key = at_precision(getattr(st, policy["sort_by"])[idx], precision)
    order = idx[np.lexsort((st.fid[idx], key))]
    fids, rl = st.fid[order], rule[order]
    out_f, out_r = [], []
    b = policy["batch_size"]
    for lo in range(0, order.size, b):
        cf, cr = fids[lo: lo + b], rl[lo: lo + b]
        for r in np.unique(cr):
            sel = cr == r
            out_f.append(cf[sel])
            out_r.append(cr[sel])
    if not out_f:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(out_f), np.concatenate(out_r)


def _sizes(st: CatalogState, precision: str) -> Tuple[np.ndarray,
                                                     np.ndarray]:
    return (at_precision(st.size.astype(np.float64), precision),
            at_precision(st.blocks.astype(np.float64), precision))


def du(st: CatalogState, vis: np.ndarray, prefix: str,
       precision: str = "f32") -> Dict[str, float]:
    m = vis & subtree(st, prefix)
    f = m & ~st.is_dir
    size, blocks = _sizes(st, precision)
    return {"count": int(m.sum()), "files": int(f.sum()),
            "volume": float(size[f].sum()), "spc_used": float(blocks[f].sum())}


def find(st: CatalogState, vis: np.ndarray, criteria: str,
         precision: str = "f32") -> np.ndarray:
    """Sorted fids of visible rows that match."""
    return np.sort(st.fid[vis & mask(parse(criteria), st, precision)])


def top_values(st: CatalogState, vis: np.ndarray, by: str, k: int,
               precision: str = "f32") -> np.ndarray:
    """The k largest values of ``by`` over visible files, descending."""
    col = at_precision(getattr(st, by).astype(np.float64), precision)
    vals = col[vis & ~st.is_dir]
    if vals.size > k:
        vals = vals[np.argpartition(vals, vals.size - k)[vals.size - k:]]
    return np.sort(vals)[::-1]


def report_user(st: CatalogState, vis: np.ndarray, user: str,
                precision: str = "f32") -> Dict[str, Dict[str, float]]:
    """Per type, count/volume/spc_used of ``user``'s visible rows."""
    m = vis & (st.owner == _index(user, "user"))
    size, blocks = _sizes(st, precision)
    out = {}
    for name, sel in (("file", m & ~st.is_dir), ("dir", m & st.is_dir)):
        if sel.any():
            out[name] = {"count": int(sel.sum()),
                         "volume": float(size[sel].sum()),
                         "spc_used": float(blocks[sel].sum())}
    return out


def user_volumes(st: CatalogState, vis: np.ndarray,
                 precision: str = "f32") -> Dict[str, Tuple[int, float]]:
    """(file count, file volume) of every owner with visible files."""
    f = vis & ~st.is_dir
    size, _ = _sizes(st, precision)
    n = int(st.owner.max()) + 1 if st.n else 0
    cnt = np.bincount(st.owner[f], minlength=n)
    vol = np.bincount(st.owner[f], weights=size[f], minlength=n)
    return {f"user{o}": (int(cnt[o]), float(vol[o]))
            for o in np.nonzero(cnt)[0].tolist()}


def rel_err(got: float, want: float) -> float:
    return abs(float(got) - float(want)) / max(abs(float(want)), 1.0)


def top_users_gap(got: Sequence[dict], vols: Dict[str, Tuple[int, float]],
                  k: int, swap_tol: float) -> Tuple[int, float]:
    """(mismatches, worst volume error) of a served top-users list.

    The served users must be the k with the largest reference volumes, in
    that order, except where two reference volumes lie within
    ``swap_tol`` (relative) of each other, closer than the served sums
    may be trusted to rank: such a pair may swap."""
    ranked = sorted(vols.items(), key=lambda kv: -kv[1][1])
    want = ranked[:k]
    bad = int(len(got) != len(want))
    worst = 0.0
    for g, (name, (cnt, vol)) in zip(got, want):
        ref = vols.get(g["user"])
        if ref is None:
            bad += 1
            continue
        if g["user"] != name and rel_err(ref[1], vol) > swap_tol:
            bad += 1
        bad += int(g["count"] != ref[0])
        worst = max(worst, rel_err(g["volume"], ref[1]))
    return bad, worst
