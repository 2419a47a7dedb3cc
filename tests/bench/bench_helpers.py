"""Helpers of the benchmark's tests: the repo root on ``sys.path`` (the
``bench`` package lives there), and harness runs at a size the CPU holds
that leave JAX's persistent compilation cache alone. (Not a conftest.py:
other test modules import the top-level one by that name.)

A cell held out of ``BENCHMARK.json`` (``held_cells.json`` beside this
file holds its entries) runs from a root whose ``BENCHMARK.json`` adds
them, so its files stay tested until it is added back. So does a test
fixture's cell (``fixtures/cells.json``), whose configuration and traffic
files (``fixtures/configs``, ``fixtures/traffic``) that root's ``bench/``
holds beside the benchmark's own."""
import json
import os
import sys
import tempfile
from pathlib import Path

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HELD = os.path.join(os.path.dirname(__file__), "held_cells.json")
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture
def no_cache(monkeypatch):
    """A harness run here must not turn the persistent cache on for the
    rest of the worker's tests."""
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")


def tiny_run(workload, seed=20240611, seconds=None, trace=False,
             controls=(), entries=20_000):
    """One harness run of a cell at a size the CPU holds; the serving
    cell's window holds a few of each kind of query."""
    import time
    from bench import harness
    if seconds is None:
        seconds = 6.0 if workload.startswith("project_reports") else 1.5
    with tempfile.TemporaryDirectory() as tmp:
        return harness.run_cell(workload, seed, seconds, trace,
                                t_start=time.perf_counter(),
                                require_tpu=False, entries=entries,
                                controls=controls,
                                root=cell_root(workload, Path(tmp)))


def cell_root(workload, tmp):
    """The repo root, or for a held or fixture cell ``tmp`` made into a
    root whose ``BENCHMARK.json`` holds it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if any(w["name"] == workload for w in bench["workloads"]):
        return Path(ROOT)
    for path in (HELD, os.path.join(FIXTURES, "cells.json")):
        with open(path) as f:
            for key, entries in json.load(f).items():
                bench[key] = bench[key] + entries
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    src = Path(ROOT, "bench")
    (tmp / "bench").mkdir()
    for p in src.iterdir():
        if p.name not in ("configs", "traffic"):
            (tmp / "bench" / p.name).symlink_to(p)
    for sub in ("configs", "traffic"):
        (tmp / "bench" / sub).mkdir()
        for f in [*(src / sub).glob("*.json"),
                  *Path(FIXTURES, sub).glob("*.json")]:
            (tmp / "bench" / sub / f.name).symlink_to(f)
    return tmp
