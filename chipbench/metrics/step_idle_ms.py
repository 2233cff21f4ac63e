"""Pool host path: mean idle time of the device between the end of one
decode program and the start of the next, every idle fragment counted,
from the trace."""
from chipbench import program_trace


def read(run):
    n, idle = program_trace.step_idle(run.reduced) if run.reduced else (0, {})
    if not n:
        return None
    total = sum(idle.values())
    run.note("step_idle_ms", f"{total / 1e9:.6f} s idle over {n} intervals between "
             "decode programs")
    return total / n / 1e6
