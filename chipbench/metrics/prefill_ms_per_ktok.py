"""Model step: prefill program device time per 1000 real prompt tokens
(padding to the program's buckets shows as a higher number)."""
PATTERN = r"\bjit_prefill_impl\b|^prefill_impl"


def read(run):
    progs = run.reduced.programs(PATTERN) if run.reduced else []
    calls = run.spans.of("prefill_request") if run.spans else []
    if not progs or not calls:
        return None
    dev = sum(d for _, _, d in progs) / len(progs)
    tokens = sum(c[3] for c in calls) / len(calls)
    return dev / 1e6 / (tokens / 1e3)
