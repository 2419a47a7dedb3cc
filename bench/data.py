"""Catalog state and churn drawn from ``--seed``: the benchmark's own arrays.

The program receives only Entries built from these arrays; the plain
reference reads the arrays themselves, so it knows the state without
asking the program. Every size, block count and time is an integer that
f32 holds exactly (the store keeps f32 columns), and ``now`` is a multiple
of 128 s, so device compares at age cut-offs in whole days are exact.

The distributions are those of the repo's chip smoke test: log-normal
sizes around 64 KiB with a multi-GB tail, exponential ages with a 60-day
mean, owners Zipf(1.3) clamped at the last owner, 8% directories.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

DAY = 86400.0
CHUNK = 200_000

# independent random streams of one seed
STREAM_CATALOG, STREAM_CHURN, STREAM_TRAFFIC, STREAM_CHECK = range(4)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """One named stream of ``seed``: any whole number, however large."""
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def f32_exact(x: np.ndarray) -> np.ndarray:
    """Round non-negative values to integers that f32 holds exactly."""
    return np.rint(x).astype(np.float32).astype(np.int64)


def f32_time(t: np.ndarray) -> np.ndarray:
    return np.asarray(t, np.float64).astype(np.float32).astype(np.float64)


@dataclasses.dataclass
class CatalogState:
    """Column arrays of the catalog, indexed by ``fid - 1``."""
    now: float
    fid: np.ndarray        # int64
    size: np.ndarray       # int64
    blocks: np.ndarray     # int64
    atime: np.ndarray      # float64 (mtime and ctime start equal to it)
    mtime: np.ndarray      # float64
    owner: np.ndarray      # int64 owner index
    group: np.ndarray      # int64 group index
    is_dir: np.ndarray     # bool
    hsm: np.ndarray        # int64 HsmState value
    subdir: np.ndarray     # int64
    path_fmt: str

    @property
    def n(self) -> int:
        return int(self.fid.size)

    def path(self, i: int) -> str:
        return self.path_fmt.format(owner=f"user{self.owner[i]}",
                                    group=f"grp{self.group[i]}",
                                    subdir=int(self.subdir[i]),
                                    fid=int(self.fid[i]))

    def copy(self) -> "CatalogState":
        return dataclasses.replace(self, **{
            f.name: getattr(self, f.name).copy()
            for f in dataclasses.fields(self)
            if isinstance(getattr(self, f.name), np.ndarray)})


def generate(cat: dict, entries: int, seed: int) -> CatalogState:
    """Draw ``entries`` rows from the configuration's distributions."""
    rng = rng_for(seed, STREAM_CATALOG)
    now = float(cat["now"])
    parts: Dict[str, List[np.ndarray]] = {k: [] for k in (
        "size", "blocks", "atime", "owner", "is_dir", "hsm", "subdir")}
    for lo in range(0, entries, CHUNK):
        k = min(CHUNK, entries - lo)
        size = f32_exact(np.minimum(
            rng.lognormal(math.log(cat["size_lognormal_median"]),
                          cat["size_lognormal_sigma"], k),
            float(cat["size_cap"])))
        parts["size"].append(size)
        parts["blocks"].append(f32_exact(np.ceil(size / 512.0)))
        age = np.minimum(rng.exponential(cat["age_mean_days"] * DAY, k),
                         cat["age_cap_days"] * DAY)
        parts["atime"].append(f32_time(now - age))
        parts["owner"].append(
            np.minimum(rng.zipf(cat["owner_zipf"], k), cat["owners"]) - 1)
        parts["is_dir"].append(rng.random(k) < cat["dir_share"])
        p = np.asarray(cat["hsm_state_p"], np.float64)
        parts["hsm"].append(rng.choice(p.size, size=k, p=p / p.sum()))
        parts["subdir"].append(rng.integers(0, cat["subdirs"], k))
    cols = {k: np.concatenate(v) if v else np.zeros(0) for k, v in
            parts.items()}
    # the first rows enumerate every (owner, type, HSM state), so that the
    # profile cube's group count, and every program shape with it, is the
    # same for every seed
    n_hsm = len(cat["hsm_state_p"])
    k = cat["owners"] * 2 * n_hsm
    if entries >= k:
        i = np.arange(k)
        cols["owner"][:k] = i // (2 * n_hsm)
        cols["is_dir"][:k] = (i // n_hsm) % 2 == 1
        cols["hsm"][:k] = i % n_hsm
    owner = cols["owner"].astype(np.int64)
    return CatalogState(
        now=now, fid=np.arange(1, entries + 1, dtype=np.int64),
        size=cols["size"].astype(np.int64),
        blocks=cols["blocks"].astype(np.int64),
        atime=cols["atime"].astype(np.float64),
        mtime=cols["atime"].astype(np.float64).copy(),
        owner=owner, group=owner % cat["groups"],
        is_dir=cols["is_dir"].astype(bool),
        hsm=cols["hsm"].astype(np.int64),
        subdir=cols["subdir"].astype(np.int64), path_fmt=cat["path"])


def owners_by_share(cat: dict) -> List[int]:
    """Owner indices from most to fewest expected entries (Zipf clamped
    at the last owner, which takes the whole tail)."""
    n, s = cat["owners"], cat["owner_zipf"]
    ks = np.arange(1, 200_001, dtype=np.float64)
    pmf = ks ** -s
    pmf /= pmf.sum()
    share = np.concatenate([pmf[: n - 1], [pmf[n - 1:].sum()]])
    return [int(i) for i in np.argsort(-share, kind="stable")]


# -- churn ------------------------------------------------------------------

def class_values(cat: dict, classes: int) -> List[Tuple[int, int, float]]:
    """(size, blocks, age) at the midpoint quantiles of the catalog's own
    size and age distributions: every seed draws from the same values, so
    the matched share stays steady and alike from seed to seed."""
    nd = NormalDist()
    out = []
    for c in range(classes):
        q = (c + 0.5) / classes
        size = int(f32_exact(np.asarray([min(
            cat["size_lognormal_median"]
            * math.exp(cat["size_lognormal_sigma"] * nd.inv_cdf(q)),
            float(cat["size_cap"]))]))[0])
        blocks = int(f32_exact(np.asarray([math.ceil(size / 512.0)]))[0])
        age = min(-cat["age_mean_days"] * DAY * math.log(1.0 - q),
                  cat["age_cap_days"] * DAY)
        out.append((size, blocks, age))
    return out


@dataclasses.dataclass
class ChurnCall:
    """One ``Catalog.update_fields_batch`` call: one value class."""
    fids: np.ndarray
    size: int
    blocks: int
    atime: float

    def fields(self) -> dict:
        return {"size": self.size, "blocks": self.blocks,
                "atime": self.atime, "mtime": self.atime}


class Churn:
    """Batches of updates that redraw size and atime: ``rows`` distinct
    entries per batch, spread over ``classes`` value classes."""

    def __init__(self, cat: dict, n_entries: int, seed: int, rows: int,
                 classes: int) -> None:
        self.rng = rng_for(seed, STREAM_CHURN)
        self.n = n_entries
        self.rows = min(rows, n_entries)
        self.classes = classes
        self.values = class_values(cat, classes)
        self.now = float(cat["now"])

    def batch(self) -> List[ChurnCall]:
        """The next batch. Each class pairs its size quantile with an age
        quantile in a seeded order; each class is one call."""
        fids = self.rng.choice(self.n, size=self.rows, replace=False) + 1
        ages = self.rng.permutation(self.classes)
        calls = []
        for c, part in enumerate(np.array_split(fids, self.classes)):
            if not part.size:
                continue
            size, blocks, _ = self.values[c]
            age = self.values[int(ages[c])][2]
            atime = float(f32_time(np.asarray([self.now - age]))[0])
            calls.append(ChurnCall(part.astype(np.int64), size, blocks,
                                   atime))
        return calls


def apply_churn(state: CatalogState, calls: List[ChurnCall]) -> None:
    """Apply a batch to the benchmark's own arrays (the reference side)."""
    for call in calls:
        i = call.fids - 1
        state.size[i] = call.size
        state.blocks[i] = call.blocks
        state.atime[i] = call.atime
        state.mtime[i] = call.atime
