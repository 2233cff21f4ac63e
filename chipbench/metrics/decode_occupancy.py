"""Scheduler: decode tokens per decode step over the window, as a share of
the decode batch (the decode pools' PhaseStats counters)."""


def read(run):
    st = run.stats
    if not st["decode_steps"]:
        return None
    return 100.0 * st["decode_tokens"] / st["decode_steps"] / run.batch
