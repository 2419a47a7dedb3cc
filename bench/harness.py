"""The harness: one run of one cell, driven by the files the cell names.

``BENCHMARK.json`` names the cell's configuration (``bench/configs/``),
its traffic mix (``bench/traffic/``) and its metrics; each metric is read
by ``bench/metrics/<name>.py``. A run builds the deployment from the seed
(set-up, warm-up included), measures the window, reads the device's peak
memory, reduces the trace when one was asked for, then compares a sample
of the window's answers, drawn from the seed, with the plain reference.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from . import data, deploy, ops, roofline
from .tracing import Tracer, TraceSummary, annotate
from .generator import Event, Traffic

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / "bench_out" / "trace"


class NoDevice(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class OpRecord:
    op: str
    request: dict
    due: float                  # perf_counter seconds
    start: float
    end: float
    n_churn: int                # churn batches committed before it ran
    answer: object = None
    fallback: int = 0
    spans: Optional[dict] = None
    error: Optional[str] = None


@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader may read."""
    workload: str
    cfg: dict
    tcfg: dict
    setup_s: float
    window_s: float
    ops: List[OpRecord]
    trace: Optional[TraceSummary]
    peaks: Optional[dict]
    policy_bytes: List[int]     # roofline bytes of each policy run
    compiles_in_window: int = 0  # backend compiles inside the window

    def of(self, *kinds: str) -> List[OpRecord]:
        return [r for r in self.ops if r.op in kinds and r.error is None]

    @property
    def queries(self) -> List[OpRecord]:
        return [r for r in self.ops if r.op != "policy_run"
                and r.error is None]


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

    def snapshot(self) -> Tuple[int, float, int]:
        return self.count, self.seconds, self.cache_hits


def say(**kv) -> None:
    print("info " + " ".join(f"{k}={v}" for k, v in kv.items()),
          file=sys.stderr, flush=True)


# -- the cell's files -----------------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: Path = ROOT):
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {', '.join(sorted(cells))}")
    wl = cells[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(root / cfgs[wl["config"]]["file"])
    tcfg = load_json(root / "bench" / "traffic" / f"{wl['traffic']}.json")
    return bench, wl, cfg, tcfg


def cell_metrics(bench: dict, workload: str, kind: str) -> List[dict]:
    """The cell's metrics of one kind ("end_to_end" or "per_layer")."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def reader(name: str, root: Path = ROOT) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str, root: Path = ROOT) -> dict:
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if kind not in table:
        raise NoDevice(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees "
                       f"{len(devs)}")
    return devs[:chips]


# -- the window -------------------------------------------------------------------

def execute(dep, ev: Event, due: float, churn_log: list
            ) -> Optional[OpRecord]:
    """Commit a churn batch (no record) or serve a request (a record)."""
    if ev.churn is not None:
        with annotate("churn"):
            for call in ev.churn:
                dep.catalog.update_fields_batch(call.fids.tolist(),
                                                **call.fields())
        churn_log.append(ev.churn)
        return None
    req = ev.request
    with annotate(req["op"]):
        start = time.perf_counter()
        rec = OpRecord(req["op"], req, due, start, start, len(churn_log))
        try:
            out = ops.RUNNERS[req["op"]](dep, req)
            rec.answer = out["answer"]
            rec.fallback = out.get("fallback", 0)
            rec.spans = out.get("spans")
        except Exception as exc:              # counted as failed, reported
            rec.error = f"{type(exc).__name__}: {exc}"
        rec.end = time.perf_counter()
    return rec


def closed_window(dep, traffic: Traffic, seconds: float, churn_log: list
                  ) -> Tuple[List[OpRecord], float]:
    records = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        for ev in traffic.step():
            rec = execute(dep, ev, time.perf_counter(), churn_log)
            if rec is not None:
                records.append(rec)
    return records, time.perf_counter() - t0


def open_window(dep, events: List[Event], seconds: float, drain_s: float,
                churn_log: list) -> Tuple[List[OpRecord], float]:
    """Serve ``events`` at their due times, FIFO, from this one thread.
    Each query is timed from when it was due; queries still queued
    ``drain_s`` after the window's end fail."""
    records = []
    t0 = time.perf_counter()
    for i, ev in enumerate(events):
        due = t0 + ev.due
        wait = due - time.perf_counter()
        if wait > 0:
            with annotate("wait"):
                time.sleep(wait)
        if time.perf_counter() - t0 > seconds + drain_s:
            for late in events[i:]:
                if late.request is not None:
                    records.append(OpRecord(
                        late.request["op"], late.request, t0 + late.due,
                        0.0, 0.0, len(churn_log),
                        error="not served before the drain limit"))
            break
        rec = execute(dep, ev, due, churn_log)
        if rec is not None:
            records.append(rec)
    return records, max(seconds, time.perf_counter() - t0)


# -- correctness ------------------------------------------------------------------

def sample(records: List[OpRecord], n: int, seed: int) -> List[int]:
    """Indices of the answers to compare: ``n`` drawn from the seed, at
    least one of each kind (each op, and each policy a request names),
    and the last."""
    ok = [i for i, r in enumerate(records) if r.error is None]
    if not ok:
        return []
    rng = data.rng_for(seed, data.STREAM_CHECK)
    order = rng.permutation(ok).tolist()
    pick = set(order[:n])
    seen = set()
    for i in order:
        kind = (records[i].op, records[i].request.get("policy"))
        if kind not in seen:
            seen.add(kind)
            pick.add(i)
    pick.add(ok[-1])
    return sorted(pick)


def readings(st0: data.CatalogState, cfg: dict, tcfg: dict,
             records: List[OpRecord], churn_log: list, seed: int,
             vol_limit: float, control: Optional[str] = None) -> dict:
    """The numbers compared with their limits.

    ``mismatches`` counts served answers (fids, counts, orders, ranks)
    that differ from the reference; ``volume_rel_err`` is the worst
    relative error of a volume sum. With ``control`` set, the reference
    at that precision is put in the program's place."""
    picks = sample(records, tcfg["check_sample"], seed)
    st = st0.copy()
    applied = 0
    bad, worst = 0, 0.0
    for i in picks:
        rec = records[i]
        while applied < rec.n_churn:
            data.apply_churn(st, churn_log[applied])
            applied += 1
        want = ops.reference(st, cfg, rec.request)
        got = rec.answer if control is None else \
            ops.reference(st, cfg, rec.request, control)
        b, e = ops.compare(st, rec.request, got, want, vol_limit)
        bad += b
        worst = max(worst, e)
    return {"mismatches": bad, "volume_rel_err": worst,
            "compared": len(picks)}


def judge(got: dict, limits: dict, failed: int, fallbacks: int
          ) -> Tuple[Dict[str, dict], bool]:
    """The numbers compared beside their limits, and ``correct``: no
    request failed, at least one answer was compared, and every number
    is within its limit."""
    checks = {name: {"value": got[name], "limit": limits[name]}
              for name in ("mismatches", "volume_rel_err") if name in limits}
    checks["fallbacks"] = {"value": fallbacks, "limit": 0}
    correct = (failed == 0 and got["compared"] >= 1
               and all(c["value"] <= c["limit"] for c in checks.values()))
    return checks, correct


# -- one run ------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    """A cell set up and warmed up, ready for its window."""
    bench: dict
    wl: dict
    cfg: dict
    tcfg: dict
    devs: list
    peaks: Optional[dict]
    counter: CompileCounter
    st0: data.CatalogState
    dep: object
    traffic: Traffic
    churn_log: list


def prepare(workload: str, seed: int, *, require_tpu: bool = True,
            entries: Optional[int] = None, root: Path = ROOT) -> Cell:
    """Set-up: the device check, the compile cache, the catalog drawn from
    the seed and loaded, the store and its planes, and the warm-up."""
    bench, wl, cfg, tcfg = load_cell(workload, root)
    devs = devices(wl["chips"], require_tpu)
    peaks = load_peaks(devs[0].device_kind, root) if require_tpu else None
    from repro.launch.compile_cache import enable_compile_cache
    say(compile_cache=enable_compile_cache())
    counter = CompileCounter()

    n = entries or cfg["entries"]
    t0 = time.perf_counter()
    st0 = data.generate(cfg["catalog"], n, seed)
    t1 = time.perf_counter()
    dep = deploy.build(cfg, st0, wl["chips"])
    t2 = time.perf_counter()
    traffic = Traffic(tcfg, cfg, dep.subjects, n, seed)
    churn_log: list = []
    for ev in traffic.warmup():
        rec = execute(dep, ev, time.perf_counter(), churn_log)
        if rec is not None and rec.error is not None:
            raise RuntimeError(f"warm-up {rec.op} failed: {rec.error}")
    t3 = time.perf_counter()
    say(setup_draw_s=t1 - t0, setup_build_s=t2 - t1, setup_warmup_s=t3 - t2,
        compiles_setup=counter.snapshot()[0])
    return Cell(bench, wl, cfg, tcfg, devs, peaks, counter, st0, dep,
                traffic, churn_log)


@dataclasses.dataclass
class Outcome:
    result: dict
    checks: Dict[str, dict]
    controls: Dict[str, dict] = dataclasses.field(default_factory=dict)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             entries: Optional[int] = None, root: Path = ROOT,
             controls: Tuple[str, ...] = ()) -> Outcome:
    """One run. ``entries`` overrides the configuration's catalog size
    (the CPU tests); ``controls`` adds, beside the program's readings,
    those of the reference at each precision put in the program's place,
    on the same sampled answers (``bench/control.py``, the tests)."""
    cell = prepare(workload, seed, require_tpu=require_tpu, entries=entries,
                   root=root)
    bench, wl, cfg, tcfg = cell.bench, cell.wl, cell.cfg, cell.tcfg
    devs, peaks, counter = cell.devs, cell.peaks, cell.counter
    st0, dep, traffic, churn_log = cell.st0, cell.dep, cell.traffic, \
        cell.churn_log
    events = traffic.schedule(seconds) if tcfg["loop"] == "open" else None
    host_served0 = dep.reports.host_served if dep.reports else 0
    gc.collect()
    setup_s = time.perf_counter() - t_start
    c0 = counter.snapshot()

    tracer = Tracer(str(TRACE_DIR / workload)) if trace else None
    if tracer:
        tracer.__enter__()
    try:
        if tcfg["loop"] == "closed":
            records, window_s = closed_window(dep, traffic, seconds,
                                              churn_log)
        else:
            records, window_s = open_window(dep, events, seconds,
                                            tcfg["drain_s"], churn_log)
    finally:
        if tracer:
            tracer.__exit__(None, None, None)
    c1 = counter.snapshot()
    fallbacks = sum(r.fallback for r in records)
    if dep.reports is not None:
        fallbacks += dep.reports.host_served - host_served0
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    summary = tracer.summary() if tracer else None
    say(setup_s=setup_s, window_s=window_s, ops=len(records),
        compiles_in_window=c1[0] - c0[0], compile_s_in_window=c1[1] - c0[1],
        cache_hits_in_window=c1[2] - c0[2], compiles_total=c1[0],
        compile_s_total=c1[1], memory_peak_bytes=peak)

    per_row = {name: roofline.row_bytes(st0, pol)
               for name, pol in deploy.policies(cfg).items()}
    policy_bytes = [roofline.match_bytes(
        st0.n, per_row[deploy.request_policy(cfg, r.request)["name"]],
        r.answer[0].size) for r in records
        if r.op == "policy_run" and r.error is None]
    dep = cell.dep = None              # free the program's state first
    gc.collect()

    limits = tcfg["limits"]
    vol_limit = limits.get("volume_rel_err", 0.0)
    got = readings(st0, cfg, tcfg, records, churn_log, seed, vol_limit)
    failed = sum(r.error is not None for r in records)
    say(answers_compared=got["compared"])
    checks, correct = judge(got, limits, failed, fallbacks)
    for r in records:
        if r.error is not None:
            say(failed_op=r.op, error=r.error.replace(" ", "_")[:200])
            break

    extra = {}
    for p in controls:
        c = readings(st0, cfg, tcfg, records, churn_log, seed, vol_limit, p)
        # the control stands in for the program's answers: judged by the
        # same rule, with no failed request or fallback of its own
        extra[p] = dict(c, correct=judge(c, limits, 0, 0)[1])
    rec = RunRecord(workload, cfg, tcfg, setup_s, window_s, records,
                    summary, peaks, policy_bytes, c1[0] - c0[0])
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, workload, kind):
        value = reader(m["name"], root)(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps(10)}
    result["checks"] = checks
    return Outcome(result, checks, extra)


def report(outcome: Outcome) -> None:
    """The result line on stdout; the compared numbers last on stderr."""
    for name, c in outcome.checks.items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(outcome.result), flush=True)


def percentile(values: List[float], q: float) -> Optional[float]:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def mean(values: List[float]) -> Optional[float]:
    return float(sum(values) / len(values)) if values else None


def span_seconds(tree: Optional[dict], name: str) -> float:
    """Summed seconds of every span called ``name`` in a span tree."""
    if not tree:
        return 0.0
    own = tree["elapsed_s"] if tree["name"] == name else 0.0
    return own + sum(span_seconds(c, name) for c in tree.get("children", []))
