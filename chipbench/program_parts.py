"""One traced run of a cell, read by the program's own spans and scopes.

    python3 chipbench/program_parts.py --workload <cell> --seed <n> --seconds <s>

It is ``run.py --trace 1`` (the same traffic, profiled sub-window, result
line and check) with three additions: the program's span recorder
(``repro.serving.spans``) is on while the profiler traces, the trace's
``repro.*`` host spans are kept (``program_trace.host_spans``), and the decode
program's compiled HLO names each op's scope. The result line gains
``program_parts``: ``program_trace.readings`` (the idle between decode
programs by span, the decode program's device time by scope) and
``idle_parts`` (that idle by the innermost span over it). With
``CHIPBENCH_KEEP_TRACE=<file>`` the kept 0.3 s of trace holds the spans
and scopes too.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from pathlib import Path
from typing import Optional, Sequence

REPO = Path(__file__).resolve().parent.parent
for p in (REPO / "src", REPO):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import program_trace, tracing  # noqa: E402
from chipbench import run as bench  # noqa: E402


def decode_hlo(fleet) -> str:
    """The compiled HLO of the decode program the first replica's (dense)
    decode pool dispatches, lowered from the pool's own arrays."""
    import numpy as np

    from repro.serving.pool import decode_jit_for

    pool = fleet.replicas[0].decode_pool
    args = (pool.params, pool._host_cur_token, pool.cache,
            pool._host_lengths.astype(np.int32), pool.active_mask(), pool._key,
            pool._slot_temp)
    return decode_jit_for(pool.cfg).lower(*args).compile().as_text()


@contextlib.contextmanager
def program_spans_in_trace():
    """While open, a traced run turns the program's recorder on with the
    profiler, and the profiler keeps the program's spans of its trace as
    ``program_spans``."""
    from repro.serving import spans

    class Profiler(tracing.Profiler):
        program_spans = []

        def tick(self, elapsed_s: float) -> None:
            before = self.state
            super().tick(elapsed_s)
            if before == "before" and self.state == "on":
                spans.clear()
                spans.enable()

        def stop(self) -> None:
            before = self.state
            spans.disable()
            super().stop()
            if before == "on":
                self.program_spans = program_trace.host_spans(self.logdir)

    saved = tracing.Profiler
    tracing.Profiler = Profiler
    try:
        yield
    finally:
        tracing.Profiler = saved
        spans.disable()
        spans.clear()


def observe(run) -> dict:
    """The program's readings of the traced window (``run_cell``'s
    ``observe``), with the program's spans and the decode program's scopes
    put into the trace."""
    red = run.reduced
    if red is None:
        return {}
    red.data["program_spans"] = run.profiler.program_spans
    out = {}
    try:
        program_trace.add_op_scopes(red.data, [decode_hlo(run.fleet)])
    except Exception as e:  # the scope readings then read nothing
        out["no_decode_hlo"] = repr(e)
    keep = os.environ.get("CHIPBENCH_KEEP_TRACE")
    if keep:
        Path(keep).write_text(json.dumps(program_trace.trim(red.data, red.lo,
                                                            red.lo + 0.3e9)))
    out["readings"] = program_trace.readings(red)
    if out["readings"].get("step_idle_ms"):
        out["idle_parts"] = program_trace.idle_parts(red)
    return out


def run_parts(workload: str, seed: int, seconds: float, **kw) -> dict:
    """``run.run_cell``'s traced run of ``workload``, with ``program_parts``."""
    import jax

    # the compilation cache's key leaves out the op metadata that holds the
    # named scopes: a cache shared with a build of other scopes would hand
    # back its program, and its scopes with it
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        with program_spans_in_trace():
            res = bench.run_cell(workload, seed, seconds, True, observe=observe, **kw)
    finally:
        jax.config.update(key, was)
    res["program_parts"] = res.pop("observed", {})
    return res


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        res = run_parts(args.workload, args.seed, args.seconds)
    except bench.NoChip as e:
        return int(e.code)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
