"""What the drivers share: the warm-up requests of a cell's traffic."""
from __future__ import annotations

import types
from typing import List

from chipbench.traffic import Spec


def warm_specs(run) -> List[Spec]:
    """One short request per prefill shape the traffic will use: for each
    bucket the program pads prompts to, a prompt of the longest length the
    traffic sends into it, with two output tokens so the decode step and
    the placement compile too. Only shapes the traffic uses are warmed."""
    pool = run.fleet.replicas[0].prefill_pool
    longest = {}
    for s in run.specs:
        _, _, bucket = pool.prefill_tokens(types.SimpleNamespace(prompt=s.prompt))
        if len(s.prompt) > len(longest.get(bucket, s.prompt[:0])):
            longest[bucket] = s.prompt
    return [Spec(prompt=p, max_new=2) for _, p in sorted(longest.items())]
