"""Pool host path: mean idle time of the device before each decode
program, from the trace."""
PATTERN = r"\bjit_decode_impl\b|^decode_impl"


def read(run):
    tr = run.reduced
    progs = tr.programs(PATTERN) if tr else []
    if not progs:
        return None
    return sum(tr.gap_before(s) for _, s, _ in progs) / len(progs) / 1e6
