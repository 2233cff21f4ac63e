"""Model step: mean device time of one decode program call."""
PATTERN = r"\bjit_decode_impl\b|^decode_impl"


def read(run):
    progs = run.reduced.programs(PATTERN) if run.reduced else []
    if not progs:
        return None
    return sum(d for _, _, d in progs) / len(progs) / 1e6
