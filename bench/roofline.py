"""Bytes a policy match must move, from the cell's data and policy alone.

The count does not depend on how the store lays the catalog out, so it
measures the same work whatever implements it: for each catalog row and
each column the policy's programs reference, the narrowest width of 1,
2, 4 or 8 bytes at which an unsigned integer, a signed integer or an
IEEE float holds every value the catalog holds for that column exactly;
plus 5 bytes per matched row (a 4-byte row id and a 1-byte rule). The
match reads columns and writes matches, so bytes bound it: the least
time is the count over the chip's HBM bandwidth (``bench/peaks.json``).
"""
from __future__ import annotations

from typing import Iterable, Set

import numpy as np

from . import reference as ref
from .data import CatalogState

MATCH_BYTES = 5          # a 4-byte row id and a 1-byte rule per match
_INTS = {1: (np.uint8, np.int8), 2: (np.uint16, np.int16),
         4: (np.uint32, np.int32), 8: (np.uint64, np.int64)}
_FLOATS = {2: np.float16, 4: np.float32, 8: np.float64}
_COLUMN = {"size": "size", "blocks": "blocks", "last_access": "atime",
           "last_mod": "mtime", "type": "is_dir", "owner": "owner",
           "group": "group", "hsm_state": "hsm"}


def width(values: np.ndarray) -> int:
    """Narrowest of 1, 2, 4, 8 bytes that holds every value exactly."""
    v = np.asarray(values)
    if v.size == 0:
        return 1
    v = v.astype(np.float64) if v.dtype.kind == "f" else v.astype(np.int64)
    lo, hi = v.min(), v.max()
    integral = bool(np.all(np.floor(v) == v))
    for w in (1, 2, 4, 8):
        if integral and any(np.iinfo(t).min <= lo and hi <= np.iinfo(t).max
                            for t in _INTS[w]):
            return w
        f = _FLOATS.get(w)
        with np.errstate(over="ignore"):
            if f is not None and np.array_equal(
                    v.astype(f).astype(np.float64), v.astype(np.float64)):
                return w
    return 8


def attributes(expr) -> Set[str]:
    """Attributes a parsed criteria expression reads."""
    if expr[0] == "cmp":
        return {expr[1]}
    return set().union(*(attributes(e) for e in expr[1:]))


def policy_columns(policy: dict) -> Set[str]:
    exprs: Iterable = [policy["scope"]] + [c for _, c in policy["rules"]]
    attrs = set().union(*(attributes(ref.parse(e)) for e in exprs))
    return {_COLUMN[a] for a in attrs}


def row_bytes(st: CatalogState, policy: dict) -> int:
    """Bytes per catalog row: the referenced columns at their widths."""
    return sum(width(getattr(st, c)) for c in sorted(policy_columns(policy)))


def match_bytes(rows: int, per_row: int, matched: int) -> int:
    """Bytes of one match over ``rows`` rows of ``per_row`` bytes."""
    return rows * per_row + MATCH_BYTES * int(matched)


def policy_bytes(st: CatalogState, policy: dict, matched: int) -> int:
    """Bytes one match of ``policy`` over ``st`` must move."""
    return match_bytes(st.n, row_bytes(st, policy), matched)
