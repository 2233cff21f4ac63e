"""Closed loop: ``clients_per_slot`` clients per decode slot, each sending
its next request when its last one finishes, until the window closes.

The loop drives the program's wall-clock fleet one round at a time
(``Fleet.submit`` and ``Fleet.step``). Clients take the mix's requests in
turn, from the first again when all were sent. The loop starts in set-up
(``ramp``) and runs until every decode slot holds a request; only then does
the window open, so the fill from an empty server is not timed. Requests
still in flight when the window closes are left unfinished.
"""
from __future__ import annotations

import itertools
import time

from chipbench.core import BenchError, Window
from chipbench.drivers_common import warm_specs

RAMP_ROUNDS = 10_000


def _submit(fleet, spec):
    return fleet.submit(spec.prompt, spec.max_new, eos_token_id=-1)


def warmup(run) -> None:
    fleet = run.fleet
    for spec in warm_specs(run):
        _submit(fleet, spec)
    while fleet.busy():
        fleet.step()


def _full(fleet, batch) -> bool:
    return all(r.decode_pool.occupancy() == batch for r in fleet.replicas)


def ramp(run) -> None:
    """Start the clients and step until every decode slot is taken."""
    fleet = run.fleet
    run.loop_specs = itertools.cycle(run.specs)
    run.submitted = [_submit(fleet, next(run.loop_specs))
                     for _ in range(run.mix["clients_per_slot"] * run.batch)]
    for _ in range(RAMP_ROUNDS):
        if _full(fleet, run.batch):
            return
        for _ in fleet.step():
            run.submitted.append(_submit(fleet, next(run.loop_specs)))
    raise BenchError(f"the decode slots did not fill in {RAMP_ROUNDS} rounds")


def measure(run) -> Window:
    fleet = run.fleet
    submitted = run.submitted
    t0 = time.perf_counter()
    deadline = t0 + run.seconds
    while True:
        now = time.perf_counter()
        if now >= deadline:
            break
        run.tick(now - t0)
        finished = fleet.step()
        if time.perf_counter() < deadline:
            for _ in finished:
                submitted.append(_submit(fleet, next(run.loop_specs)))
    return Window(t0=t0, seconds=run.seconds, requests=submitted,
                  open_loop=False)
