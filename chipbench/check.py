"""How ``correct`` is decided: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests (at least the mix's ``check.requests`` of them and
``check.tokens`` served tokens), drawn from the seed and always holding
the one with the most served tokens, is run through the float32 reference
in one causal pass per layer over each whole sequence (prompt and served
tokens). For each served token the reading is the reference's best logit
at that position minus the reference's logit of the served token: 0 where
the program chose the reference's first choice. The run is correct when the
widest such gap is under the configuration's ``max_logit_gap`` and the
sample holds at least the mix's ``check.tokens`` served tokens.

The control puts the reference in the program's place in float8 (e4m3,
one scale per tensor): at each position of the same sequences, its reading
is the reference's best logit minus the reference's logit of the token the
control puts first.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np


def sample(finished: List[Any], seed: int, tokens: int, requests: int) -> List[Any]:
    """The longest finished request, then others in an order drawn from the
    seed, until the sample holds ``requests`` requests and ``tokens`` served
    tokens, or every finished request. Requests in flight together sit in
    different slots, so several of them cover a fault confined to a few."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.output), len(r.prompt)))
    rest = [r for r in finished if r is not longest]
    order = np.random.default_rng([int(seed) & (2**63 - 1), 7]).permutation(len(rest))
    out, n = [longest], len(longest.output)
    for i in order:
        if n >= tokens and len(out) >= requests:
            break
        out.append(rest[i])
        n += len(rest[i].output)
    return out


def sequences(reqs: List[Any]):
    """Token batch (n, T) of prompt + served tokens but the last, the
    positions whose logits predict each served token, and those tokens."""
    seqs = [np.concatenate([np.asarray(r.prompt, np.int32),
                            np.asarray(r.output[:-1], np.int32)]) for r in reqs]
    t = max(len(s) for s in seqs)
    batch = np.zeros((len(seqs), t), np.int32)   # right padding: causal, unread
    rows, served = [], []
    for i, (s, r) in enumerate(zip(seqs, reqs)):
        batch[i, :len(s)] = s
        p = len(r.prompt)
        rows.extend((i, p - 1 + j) for j in range(len(r.output)))
        served.extend(int(x) for x in r.output)
    return batch, np.asarray(rows), np.asarray(served, np.int32)


def readings(model: Dict, ref: Any, seed: int, reqs: List[Any],
             control: bool = False) -> Dict[str, Optional[float]]:
    """``max_logit_gap`` of the served tokens, the ``tokens`` checked, and
    with ``control`` the control's ``control_gap``."""
    import jax
    import jax.numpy as jnp

    from chipbench import refmath as rm
    from chipbench.weights import root_key

    if not reqs:
        return {"max_logit_gap": None, "tokens": 0}
    batch, rows, served = sequences(reqs)
    root = root_key(seed)
    with jax.default_matmul_precision("highest"):
        hidden, table = ref.final_hidden(model, root, batch)
        h = hidden[rows[:, 0], rows[:, 1]]
        ctrl_h = ctrl_table = None
        if control:
            ch, ctrl_table = ref.final_hidden(model, root, batch, quant="fp8")
            ctrl_h = ch[rows[:, 0], rows[:, 1]]
        gaps, ctrl = rm.score_rows(h, table, served, ctrl_h, ctrl_table,
                                   "fp8" if control else None)
    out = {"max_logit_gap": float(np.max(gaps)), "tokens": int(len(served)),
           "argmax_share": float(np.mean(gaps == 0.0))}
    if control:
        out["control_gap"] = float(np.max(ctrl))
    del hidden, table
    jnp.zeros(()).block_until_ready()
    return out
