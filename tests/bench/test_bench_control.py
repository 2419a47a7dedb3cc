"""The comparison that decides ``correct``, at a size the CPU holds: the
program's answers read within every limit, and the control, the plain
reference at bfloat16 put in the program's place, reads beyond one."""
import pytest

from bench_helpers import no_cache, tiny_run  # noqa: F401

CELLS = ["scratch_purge.churn1", "project_reports.scoped_steady"]


@pytest.mark.parametrize("workload", CELLS)
def test_program_is_correct(no_cache, workload):
    out = tiny_run(workload)
    assert out.result["correct"], out.checks
    assert out.result["failed"] == 0
    assert out.checks["mismatches"]["value"] == 0
    assert list(out.result)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_bf16_control_fails_a_limit(no_cache, workload):
    out = tiny_run(workload, controls=("bf16",))
    control = out.controls["bf16"]
    assert control["correct"] is False, control
    assert any(control[k] > c["limit"] for k, c in out.checks.items()
               if k in control)


def test_traced_run_reports_per_layer_metrics_it_can_read(no_cache):
    out = tiny_run("scratch_purge.churn1", trace=True)
    m = out.result["metrics"]
    # host spans are read on any platform; device metrics only from a
    # device trace, which a CPU run does not have
    assert {"refresh_ms", "combine_ms", "plan_act_ms"} <= set(m)
    assert "match_kernel_ms" not in m and "setup_s" not in m
    assert out.result["correct"]
