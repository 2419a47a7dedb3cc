"""policy_run_s: the summed wall time of every PolicyEngine.run in the
window (trigger to planned and actioned purge, the refresh it triggers
included), divided by the number of runs. Host clock; no run left out."""
from bench.harness import mean


def read(rec):
    return mean([r.end - r.start for r in rec.of("policy_run")])
