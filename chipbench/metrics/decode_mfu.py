"""Whole step: the least time of the traced decode steps' work at the
chip's peaks over their host-clock wall time, from dispatch until the
tokens are on the host (``Pool.decode_once``)."""
from chipbench.core import least_time_s


def read(run):
    calls = [c for c in (run.spans.of("decode_once") if run.spans else [])
             if c[3][0] == "decode" and c[3][1]]
    if not calls:
        return None
    least = sum(least_time_s(*run.wk.decode(run.model, c[3][1]), run.peaks)
                for c in calls)
    return 100.0 * least / sum(c[2] - c[1] for c in calls)
