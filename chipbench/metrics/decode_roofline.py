"""Step programs: least time of a decode step's work at the chip's peaks
(``work/``: the larger of FLOPs over peak FLOP/s and needed bytes over peak
bandwidth) over the decode program's device time, as means over the traced
calls. ``bound`` says which of the two set the least time."""
from chipbench.core import bound_of, least_time_s

PATTERN = r"\bjit_decode_impl\b|^decode_impl"


def read(run):
    progs = run.reduced.programs(PATTERN) if run.reduced else []
    calls = [c for c in (run.spans.of("decode_once") if run.spans else [])
             if c[3][0] == "decode" and c[3][1]]
    if not progs or not calls:
        return None
    works = [run.wk.decode(run.model, c[3][1]) for c in calls]
    least = sum(least_time_s(f, b, run.peaks) for f, b in works) / len(works)
    dev = sum(d for _, _, d in progs) / len(progs) / 1e9
    run.note("decode_roofline", bound_of(*works[len(works) // 2], run.peaks) + " bound")
    return 100.0 * least / dev
