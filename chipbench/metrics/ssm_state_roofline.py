"""Step programs: the Mamba2 state and conv window bytes a decode step
reads and writes for its active slots (``work/``) at peak HBM bandwidth,
over the device time per decode call of the ops under ``ssm`` and, inside
it, ``kv_write`` (the state and conv window updates)."""
from chipbench import core


def _under_state_write(path: str) -> bool:
    parts = path.split("/")
    return "ssm" in parts and "kv_write" in parts[parts.index("ssm"):]


def read(run):
    got = core.metric_reader("decode_ssm_ms").ssm_times(run)
    calls = [c for c in (run.spans.of("decode_once") if run.spans else [])
             if c[3][0] == "decode" and c[3][1]]
    if got is None or not calls:
        return None
    n_calls, by_path = got
    dev_s = sum(v for p, v in by_path.items() if _under_state_write(p)) / n_calls
    if not dev_s:
        return None
    slots = sum(len(c[3][1]) for c in calls) / len(calls)
    nbytes = 2 * slots * run.wk.state_bytes_per_seq(run.model)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / dev_s
