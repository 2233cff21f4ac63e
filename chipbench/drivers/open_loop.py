"""Open loop: requests arrive at their due times whatever the server does.

The window replays the mix's arrivals over ``[0, seconds)`` through the
program's wall-clock replay, ``Fleet.run_trace`` (its barrier driver), which
stamps each request's arrival at its due time and sleeps across idle gaps.
After the last arrival the fleet drains for at most the mix's ``drain_s``;
a request unfinished then has failed.
"""
from __future__ import annotations

import time

from chipbench.core import Window
from chipbench.drivers_common import warm_specs


class _Cut(Exception):
    """The drain's time is up."""


def _trace(specs):
    from repro.core.traces import TracedRequest

    return [TracedRequest(arrival_s=s.due_s, prompt=s.prompt,
                          max_new_tokens=s.max_new, eos_token_id=-1)
            for s in specs]


def warmup(run) -> None:
    run.fleet.run_trace(_trace(warm_specs(run)), engine="barrier")


def measure(run) -> Window:
    fleet = run.fleet
    submitted = []
    submit, step = fleet.submit, fleet.step
    t0 = time.perf_counter()
    cut_at = t0 + run.seconds + run.mix["drain_s"]

    def counted_submit(*a, **k):
        req = submit(*a, **k)
        submitted.append(req)
        return req

    def bounded_step():
        now = time.perf_counter()
        run.tick(now - t0)
        if now >= cut_at:
            raise _Cut
        return step()

    fleet.submit, fleet.step = counted_submit, bounded_step
    try:
        fleet.run_trace(_trace(run.specs), engine="barrier")
    except _Cut:
        pass
    finally:
        del fleet.submit, fleet.step
    return Window(t0=t0, seconds=run.seconds, requests=submitted,
                  open_loop=True)
