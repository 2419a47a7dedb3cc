"""The trace reduction: busy time, kernel time by name and the breakdown,
on hand-made events and on a small trace recorded on a TPU v5e."""
import glob
import os

import pytest

import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
from bench.tracing import Event, TraceSummary, reduce_trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(os.path.dirname(os.path.dirname(HERE)), "bench",
                        "testdata")


KERNEL = '%k.1 = f32[4] custom-call(), custom_call_target="tpu_custom_call"'


def _summary():
    ops = [Event(0, "%fusion.1 = f32[8] fusion()", "jit_scatter", 0, 100),
           Event(0, KERNEL, "jit_mesh_policy_scan_batch",
                 50, 100),            # overlaps the first: busy 0-150
           Event(0, "%copy.2 = f32[8] copy()", "jit_mesh_policy_scan_batch",
                 400, 100),
           Event(1, KERNEL, "jit_mesh_policy_scan_batch", 0, 300)]
    host = [("bench.policy_run", 0, 1000), ("bench.churn", 160, 200)]
    return TraceSummary(ops, host, n_devices=2, window_s=1e-6)


def test_busy_is_the_union_of_op_intervals_averaged_over_devices():
    s = _summary()
    # device 0: [0,150] + [400,500] = 250 ns; device 1: 300 ns
    assert s.busy_s() == pytest.approx((250 + 300) / 2 / 1e9)


def test_kernel_seconds_match_programs_and_instructions():
    s = _summary()
    assert s.seconds(r"^jit_mesh_policy_scan_batch$", r"tpu_custom_call") \
        == pytest.approx(400 / 2 / 1e9)
    assert s.seconds(r"mesh_policy_scan") == pytest.approx(500 / 2 / 1e9)
    assert s.seconds(r"nothing_like_this") == 0.0
    assert s.matching(r"scatter")[0].short == "%fusion.1"


def test_breakdown_lists_ops_and_idle_gaps_by_host_activity():
    s = _summary()
    top = dict((k, v) for k, v in s.top_ops())
    assert top["jit_mesh_policy_scan_batch:%k.1"] == pytest.approx(400e-9)
    gaps = dict((k, v) for k, v in s.idle_gaps())
    # device 0 idles from 150 to 400; the middle (275) lies in the churn
    assert gaps == {"bench.churn": pytest.approx(250e-9 / 2)}


def test_recorded_tpu_trace():
    files = glob.glob(os.path.join(RECORDED, "*.xplane.pb"))
    assert files, "the recorded trace is checked in under bench/testdata"
    s = reduce_trace(files[0], window_s=10.406250696)
    assert s.n_devices == 1
    # 23 policy runs of the purge cell on one v5e: each one scatter
    # program and one match program, every operation inside a program
    assert all(e.program for e in s.ops)
    assert 0 < s.busy_s() < s.window_s
    program = _reader_constant("match_kernel_ms", "PROGRAM")
    kernel = _reader_constant("match_kernel_ms", "KERNEL")
    assert len(s.matching(program, kernel)) == 23
    assert 0 < s.seconds(program, kernel) < s.seconds(program)
    gaps = dict(s.idle_gaps())
    assert set(gaps) <= {"bench.policy_run", "bench.churn",
                         "no_annotation"}
    assert sum(gaps.values()) == pytest.approx(
        s.window_s - s.busy_s(), rel=0.1)


def _reader_constant(metric, name):
    import importlib.util
    path = os.path.join(os.path.dirname(RECORDED), "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"_t_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, name)
