"""95th percentile of every gap between consecutive tokens of a request:
in an open loop over every request due in the window, in a closed loop over
the gaps that end inside the window."""
from chipbench.core import percentile


def read(run):
    w = run.window
    gaps = []
    for r in w.counted():
        st = w.stamps(r)
        gaps.extend(b - a for a, b in zip(st, st[1:])
                    if w.open_loop or w.t0 <= b < w.t1)
    return 1e3 * percentile(gaps, 95) if gaps else None
