"""The one general traffic generator: it reads a traffic file (its
parameters) and yields the run's operations, all drawn from ``--seed``.

Two loops exist. ``closed`` repeats the file's ``step`` (an untimed churn
commit, then timed operations) back to back until the window closes; a
``policy_run`` step may name one of the configuration's policies.
``open`` sends queries on a schedule whatever the system does: Poisson
arrivals at ``rate_per_s``, each query's kind from ``mix`` and its subject
from a Zipf law over the configuration's subjects, with churn batches
committed at their own due times.

To keep the work alike from seed to seed, every seed draws the same
multiset in each block and only the order differs: gaps are the
midpoint quantiles of the exponential law, kinds come in blocks with the
mix's exact counts, and subjects in blocks with the Zipf law's counts.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterator, List, Optional

import numpy as np

from . import ops
from .data import STREAM_TRAFFIC, Churn, ChurnCall, rng_for


@dataclasses.dataclass
class Event:
    due: float                  # seconds after the window opens
    churn: Optional[List[ChurnCall]] = None
    request: Optional[dict] = None


def largest_remainder(weights: np.ndarray, total: int) -> np.ndarray:
    """Whole counts summing to ``total`` in proportion to ``weights``."""
    exact = weights / weights.sum() * total
    counts = np.floor(exact).astype(np.int64)
    short = total - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


class Traffic:
    """The operations of one run of one cell."""

    def __init__(self, tcfg: dict, cfg: dict, subjects: List[dict],
                 n_entries: int, seed: int) -> None:
        self.t = tcfg
        self.cfg = cfg
        self.subjects = subjects
        self.rng = rng_for(seed, STREAM_TRAFFIC)
        churn = self._churn_spec()
        # a catalog cut below the configuration's size (the CPU tests)
        # churns the same share of it
        rows = max(1, round(churn["share"] * n_entries)) if churn else 0
        self.churn = Churn(cfg["catalog"], n_entries, seed, rows,
                           churn["classes"]) if churn else None

    def _churn_spec(self) -> Optional[dict]:
        """{share, classes}: the share of the catalog one churn batch
        redraws, in that many value classes."""
        if self.t["loop"] == "closed":
            for op in self.t["step"]:
                if op["op"] == "churn":
                    return op
            return None
        c = self.t.get("churn")
        if not c:
            return None
        rows = c["rows_per_s"] / c["batches_per_s"]
        return {"share": rows / self.cfg["entries"], "classes": c["classes"]}

    # -- closed loop --------------------------------------------------------
    def step(self) -> List[Event]:
        """One step of the closed loop: churn batches and requests."""
        out = []
        for op in self.t["step"]:
            if op["op"] == "churn":
                out.append(Event(0.0, churn=self.churn.batch()))
            else:
                req = {"op": op["op"]}
                if "policy" in op:
                    req["policy"] = op["policy"]
                out.append(Event(0.0, request=req))
        return out

    # -- open loop ------------------------------------------------------------
    def _blocks(self, values: list, counts: np.ndarray) -> Iterator:
        pool = np.repeat(np.arange(len(values)), counts)
        while True:
            for i in self.rng.permutation(pool):
                yield values[int(i)]

    def _gaps(self) -> Iterator[float]:
        b = self.t["gap_block"]
        q = (np.arange(b) + 0.5) / b
        gaps = -np.log1p(-q) / self.t["rate_per_s"]
        while True:
            yield from self.rng.permutation(gaps).tolist()

    def request(self, kind: str, subject: dict) -> dict:
        req = ops.MAKERS[kind](self.rng, subject, self.t,
                               self.cfg["catalog"])
        req["subject"] = subject
        return req

    def schedule(self, seconds: float) -> List[Event]:
        """Every event due in ``[0, seconds)``, in due order."""
        mix = self.t["mix"]
        kinds = self._blocks(list(mix), np.asarray(list(mix.values())))
        ranks = np.arange(1, len(self.subjects) + 1, dtype=np.float64)
        subj = self._blocks(self.subjects, largest_remainder(
            ranks ** -self.t["subject_zipf"], self.t["subject_block"]))
        events = []
        due = 0.0
        for gap in self._gaps():
            due += gap
            if due >= seconds:
                break
            events.append(Event(due, request=self.request(next(kinds),
                                                          next(subj))))
        if self.churn is not None:
            period = 1.0 / self.t["churn"]["batches_per_s"]
            for i in range(int(math.ceil(seconds / period))):
                events.append(Event(i * period, churn=self.churn.batch()))
        events.sort(key=lambda e: (e.due, e.request is not None))
        return events

    def warmup(self) -> List[Event]:
        """Set-up traffic that runs every program the window will: the
        closed loop's ``warmup_steps`` steps, or queries of every kind and
        find template, churn batches of each size a refresh may scatter,
        and ``warmup_queries`` queries drawn like the window's."""
        out: List[Event] = []
        if self.t["loop"] == "closed":
            for _ in range(self.t.get("warmup_steps", 1)):
                out.extend(self.step())
            return out
        subj = self.subjects
        # queries of every kind and find template first: they build the
        # cube, the permission bitsets and the report programs
        for tpl in self.t.get("find_templates", []):
            fill = {k: v[0] for k, v in tpl.items() if k != "criteria"}
            out.append(Event(0.0, request={
                "op": "find", "criteria": tpl["criteria"].format(**fill),
                "subject": subj[0]}))
        if "profile" in self.t["mix"]:
            user = f"user{ops.user_pool(subj[0], self.cfg['catalog'])[0]}"
            out.append(Event(0.0, request={"op": "report_user", "user": user,
                                           "subject": subj[0]}))
            out.append(Event(0.0, request={
                "op": "top_users", "k": self.t["profile"]["top_users_k"],
                "subject": subj[0]}))
        # then a churn batch of each size a refresh may scatter, each
        # followed by a query whose refresh scatters it
        probe = {"op": "du", "path": ops.dir_path(self.cfg["catalog"]["path"]),
                 "subject": subj[0]}
        for rows in self.t.get("warmup_churn_rows", []):
            if self.churn is not None:
                saved, self.churn.rows = self.churn.rows, rows
                out.append(Event(0.0, churn=self.churn.batch()))
                self.churn.rows = saved
            out.append(Event(0.0, request=dict(probe)))
        kinds = list(self.t["mix"])
        for i in range(self.t.get("warmup_queries", 0)):
            out.append(Event(0.0, request=self.request(
                kinds[i % len(kinds)], subj[i % len(subj)])))
        return out
