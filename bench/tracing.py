"""Profiler trace of the measured window, and its reduction to numbers.

``Tracer`` records the window with JAX's profiler (the Python tracer off,
host annotations on). ``reduce_trace`` reads the ``.xplane.pb`` it wrote
with ``jax.profiler.ProfileData`` and keeps, per device plane, the
operation events (the ``XLA Ops`` line) and the program events (the
``XLA Modules`` line), and the benchmark's own host annotations
(``bench.*``) for what the host was doing. ``TraceSummary`` turns those
into busy time, kernel time by name and the breakdown of the result line.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil
import time
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
HOST_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    device: int
    name: str          # the HLO instruction's text, as the trace names it
    program: str       # the jitted program it ran in ("" if none)
    start_ns: float
    dur_ns: float

    @property
    def short(self) -> str:
        """The instruction's name: its text before `` = ``."""
        return self.name.split(" = ", 1)[0]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _union_ns(spans: Sequence[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(spans):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


@dataclasses.dataclass
class TraceSummary:
    """The reduced trace of one window."""
    ops: List[Event]
    host: List[Tuple[str, float, float]]    # (annotation, start_ns, dur_ns)
    n_devices: int
    window_s: float

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.n_devices:
            return 0.0
        per = {}
        for e in self.ops:
            per.setdefault(e.device, []).append((e.start_ns, e.end_ns))
        return sum(_union_ns(v) for v in per.values()) / 1e9 / self.n_devices

    def matching(self, program: str, op: str = "") -> List[Event]:
        """Operation events of the programs whose name matches ``program``
        whose instruction text matches ``op``."""
        rp, ro = re.compile(program), re.compile(op)
        return [e for e in self.ops
                if rp.search(e.program) and ro.search(e.name)]

    def seconds(self, program: str, op: str = "") -> float:
        """Summed device seconds of the matching operations, averaged over
        the devices."""
        if not self.n_devices:
            return 0.0
        return sum(e.dur_ns for e in self.matching(program, op)) / 1e9 \
            / self.n_devices

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``program:instruction`` pairs that took most device time."""
        tot: Dict[str, float] = {}
        for e in self.ops:
            key = f"{e.program}:{e.short}" if e.program else e.short
            tot[key] = tot.get(key, 0.0) + e.dur_ns / 1e9
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle device time between operations, summed by the benchmark's
        host annotation (they do not nest) that covered the middle of
        each gap."""
        if not self.ops:
            return []
        host = sorted(self.host, key=lambda h: h[1])
        starts = [h[1] for h in host]
        tot: Dict[str, float] = {}
        per: Dict[int, list] = {}
        for e in self.ops:
            per.setdefault(e.device, []).append((e.start_ns, e.end_ns))
        for spans in per.values():
            spans.sort()
            end = spans[0][1]
            for s, e in spans[1:]:
                if s > end:
                    mid = (s + end) / 2
                    i = bisect.bisect_right(starts, mid) - 1
                    label = host[i][0] if i >= 0 and \
                        mid <= host[i][1] + host[i][2] else "no_annotation"
                    tot[label] = tot.get(label, 0.0) + (s - end) / 1e9
                end = max(end, e)
        scale = 1.0 / max(1, len(per))
        return [[k, v * scale] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def _programs(ops: List[Event], modules: List[Event]) -> None:
    """Name each operation's program: the ``XLA Modules`` event on its
    device whose interval holds the operation's start."""
    by_dev: Dict[int, List[Event]] = {}
    for m in modules:
        by_dev.setdefault(m.device, []).append(m)
    for mods in by_dev.values():
        mods.sort(key=lambda m: m.start_ns)
    starts = {d: [m.start_ns for m in mods] for d, mods in by_dev.items()}
    for e in ops:
        mods = by_dev.get(e.device)
        if not mods:
            continue
        i = bisect.bisect_right(starts[e.device], e.start_ns) - 1
        if i >= 0 and e.start_ns <= mods[i].end_ns:
            e.program = mods[i].program


def reduce_trace(path: str, window_s: float) -> TraceSummary:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: List[Event] = []
    modules: List[Event] = []
    host: List[Tuple[str, float, float]] = []
    devices = set()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(2))
            devices.add(dev)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        ops.append(Event(dev, ev.name, "", ev.start_ns,
                                         ev.duration_ns))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        # "jit_name(fingerprint)": keep the jitted name
                        modules.append(Event(dev, ev.name,
                                             ev.name.split("(", 1)[0],
                                             ev.start_ns, ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.name, ev.start_ns, ev.duration_ns))
    _programs(ops, modules)
    return TraceSummary(ops, host, len(devices), window_s)


class Tracer:
    """Profiles one window into a fixed directory inside the checkout."""

    def __init__(self, out_dir: str) -> None:
        self.out_dir = out_dir
        self.t0 = 0.0

    def __enter__(self) -> "Tracer":
        import jax
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        import jax
        jax.profiler.stop_trace()
        self.window_s = time.perf_counter() - self.t0
        return False

    def summary(self) -> Optional[TraceSummary]:
        files = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            return None
        return reduce_trace(files[-1], self.window_s)


def annotate(name: str):
    """A host annotation the trace attributes idle device time to."""
    import jax
    return jax.profiler.TraceAnnotation(HOST_PREFIX + name)
