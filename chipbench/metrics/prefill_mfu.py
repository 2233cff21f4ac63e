"""Whole step: the least time of the traced prefills' work at the chip's
peaks over their host-clock wall time (``Pool.prefill_request``: inputs,
program, first token on the host)."""
from chipbench.core import least_time_s


def read(run):
    calls = run.spans.of("prefill_request") if run.spans else []
    if not calls:
        return None
    least = sum(least_time_s(*run.wk.prefill(run.model, c[3]), run.peaks)
                for c in calls)
    return 100.0 * least / sum(c[2] - c[1] for c in calls)
