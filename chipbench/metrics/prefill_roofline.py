"""Step programs: least time of a prefill's work (its real prompt tokens)
at the chip's peaks over the prefill program's device time, as means over
the traced calls."""
from chipbench.core import bound_of, least_time_s

PATTERN = r"\bjit_prefill_impl\b|^prefill_impl"


def read(run):
    progs = run.reduced.programs(PATTERN) if run.reduced else []
    calls = run.spans.of("prefill_request") if run.spans else []
    if not progs or not calls:
        return None
    works = [run.wk.prefill(run.model, c[3]) for c in calls]
    least = sum(least_time_s(f, b, run.peaks) for f, b in works) / len(works)
    dev = sum(d for _, _, d in progs) / len(progs) / 1e9
    run.note("prefill_roofline", bound_of(*works[len(works) // 2], run.peaks) + " bound")
    return 100.0 * least / dev
