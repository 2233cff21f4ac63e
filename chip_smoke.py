"""Chip smoke: serve qwen3-4b at full width on a TPU and check every token.

    python chip_smoke.py              # one chip: a dense pass, then a paged pass
    python chip_smoke.py --chips 4    # four chips: four replicas behind jsq

The one-chip run drives the serving path a user calls — ``FleetSpec`` ->
``Fleet.from_spec`` -> ``Fleet.run_trace`` on the event engine — with one
qwen3-4b replica at its published widths, bf16 weights drawn from
``--seed``, clock mode ``lock``, priced with the ``HardwareSpec`` of the
device kind JAX reports. It replays 8 requests (prompts of 64-1024 tokens,
32 greedy new tokens each) twice: on a dense decode pool (batch 8,
``max_seq_len`` 2048), then on a paged pool (16-token blocks, the
dense-equivalent block budget), freeing the first fleet before building the
second.

``--chips 4`` runs only the four-chip phase: four replicas, one per chip,
each chip holding its own copy of the weights, behind ``jsq``, with the
replica-batched decode laid out over the four chips (``shard_map``). The
trace is aligned (equal prompts, one arrival instant), so decode steps of
all four replicas fuse; every fused step must run under ``shard_map``.

Every request is checked against a plain model-level reference in the same
process: ``models.prefill`` + ``decode_step`` at batch 1 on a dense cache,
teacher-forced on the served tokens. A served token passes when it is the
reference's argmax, or when the reference scores it within ``LOGIT_TOL`` of
its maximum. Every request must finish with all its tokens.

Earlier lines report the device, parameter and cache bytes, compile seconds
and persistent-cache hits, peak device memory and the per-request check;
wall times are smoke timings, not benchmarks. The last line of stdout is
one JSON object, ``{"ok": true, "device": {...}}``. Without a TPU the
script exits non-zero before printing it; any failed check raises.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, Optional, Sequence

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-4b"
N_REQUESTS = 8
MAX_NEW = 32
PROMPT_LEN = (64, 1024)
# The aligned phase's prompts are as long as a replica's admission credit
# per decode step (ReplicaSpec.prefill_chunk_tokens, 256): a replica's
# second request then waits in its queue, where JSQ counts it, so the 8
# requests spread two per replica and the four replicas step in lockstep.
ALIGNED_PROMPT_LEN = 256
MAX_SEQ_LEN = 2048
DECODE_BATCH = 8
KV_BLOCK = 16
# A served token that is not the reference's argmax still passes when the
# reference scores it within LOGIT_TOL of its maximum logit. Logits are
# computed from bf16 hidden states (std about 1 at these widths); 0.1 is
# about three bf16 steps at the size of the top logit, far below the gap
# to a token the model does not favour.
LOGIT_TOL = 0.1


class SmokeFailure(AssertionError):
    """A check of the smoke failed."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ----------------------------------------------------------------- traffic
def make_trace(cfg, *, seed: int, n: int = N_REQUESTS, max_new: int = MAX_NEW,
               prompt_len: Sequence[int] = PROMPT_LEN, rate_rps: float = 8.0,
               aligned: bool = False):
    """``n`` requests of ``max_new`` greedy tokens each, never cut short by
    an EOS token. Prompts come from ``make_prompts``; arrivals are Poisson
    at ``rate_rps``, or all at t=0 with ``aligned``."""
    import numpy as np

    from repro.core.traces import TracedRequest, poisson_arrivals
    from repro.training.data import make_prompts

    prompts = make_prompts(cfg, n, prompt_len[0], prompt_len[1], seed=seed)
    times = (np.zeros(n) if aligned
             else poisson_arrivals(n, rate_rps, np.random.default_rng(seed)))
    return [TracedRequest(arrival_s=float(t), prompt=p, max_new_tokens=max_new,
                          eos_token_id=-1)
            for t, p in zip(times, prompts)]


# --------------------------------------------------------------- reference
def _pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


class Reference:
    """The model-level reference: ``prefill`` + ``decode_step`` at batch 1
    on a dense cache of ``max_seq_len`` rows, teacher-forced on the served
    tokens. Prompts are padded to a power of two (``prompt_lengths`` marks
    the real length), so there is one prefill program per length class."""

    def __init__(self, cfg, params, max_seq_len: int):
        import jax

        from repro.models import decode_step, init_cache, prefill

        self.cfg, self.params, self.max_seq_len = cfg, params, max_seq_len
        self._prefill = jax.jit(
            lambda p, toks, n: prefill(p, cfg, toks, init_cache(cfg, 1, max_seq_len),
                                       prompt_lengths=n))
        self._decode = jax.jit(
            lambda p, tok, cache, n: decode_step(p, cfg, tok, cache, n),
            donate_argnums=(2,))

    def check(self, prompt, served: Sequence[int]) -> Dict[str, Any]:
        """Per-token check of ``served`` (the request's output) against the
        reference; returns the argmax matches and the worst margin."""
        import jax.numpy as jnp
        import numpy as np

        toks = np.zeros((1, min(_pow2(len(prompt)), self.max_seq_len)), np.int32)
        toks[0, :len(prompt)] = prompt
        logits, cache, lengths = self._prefill(
            self.params, toks, np.asarray([len(prompt)], np.int32))
        margins, argmax_hits = [], 0
        for i, tok in enumerate(served):
            row = logits[0]
            top = int(jnp.argmax(row))
            margins.append(float(row[top] - row[tok]))
            argmax_hits += top == tok
            if i + 1 < len(served):
                logits, cache, lengths = self._decode(
                    self.params, np.asarray([tok], np.int32), cache, lengths)
        worst = max(margins)
        return {"tokens": len(served), "argmax": argmax_hits,
                "max_margin": worst, "ok": worst <= LOGIT_TOL}


# ---------------------------------------------------------- serve + check
def serve_and_check(arch: str, params, emodel, trace, ref: Reference, *,
                    reduced: bool, paged: bool, max_seq_len: int = MAX_SEQ_LEN,
                    batch: int = DECODE_BATCH, replicas: int = 1,
                    devices: Optional[Sequence[Any]] = None,
                    engine_opts: Optional[Dict[str, Any]] = None,
                    log=print) -> Dict[str, Any]:
    """Replay ``trace`` through ``Fleet.run_trace`` (event engine) on
    ``replicas`` replicas of ``arch``, then check every request against the
    model-level reference ``ref``. Raises ``SmokeFailure`` on any failed
    check; returns the engine counters and the check summary. The fleet is
    freed before the reference runs."""
    import numpy as np

    from repro.serving import ClockSpec, Fleet, FleetSpec, PoolSpec, ReplicaSpec

    spec = FleetSpec(
        replicas=tuple(
            ReplicaSpec(name=f"r{i}", arch=arch, clock=ClockSpec(mode="lock"),
                        decode=PoolSpec(batch=batch, paged=paged,
                                        kv_block_size=KV_BLOCK),
                        max_seq_len=max_seq_len, reduced=reduced)
            for i in range(replicas)),
        router="jsq", engine_opts=dict(engine_opts or {}))
    fleet = Fleet.from_spec(spec, emodel=emodel, params_for={arch: params},
                            devices=devices)
    t0 = time.perf_counter()
    done = fleet.run_trace(trace)
    wall = time.perf_counter() - t0
    stats = fleet.last_engine_stats
    served = {r.prompt.tobytes(): list(r.output) for r in done}
    del fleet, done
    gc.collect()
    log(f"  served {len(served)} requests; smoke timing (not a benchmark): "
        f"replay {wall:.3f} s")

    _require(len(served) == len(trace),
             f"{len(served)} of {len(trace)} requests finished")
    checks = []
    for i, tr in enumerate(trace):
        out = served[np.asarray(tr.prompt, np.int32).tobytes()]
        _require(len(out) == tr.max_new_tokens,
                 f"request {i}: {len(out)} of {tr.max_new_tokens} tokens")
        c = ref.check(tr.prompt, out)
        log(f"  request {i}: prompt {len(tr.prompt)} tokens, {c['tokens']} served, "
            f"{c['argmax']} reference argmax, max margin {c['max_margin']:.4f} "
            f"(tol {LOGIT_TOL}) -> {'pass' if c['ok'] else 'FAIL'}")
        checks.append(c)
    _require(all(c["ok"] for c in checks),
             f"served tokens off the reference by more than {LOGIT_TOL} logits")
    return {"stats": stats, "checks": checks}


# -------------------------------------------------------------- reporting
class CompileLog:
    """Backend compile seconds and persistent-cache hits and misses, from
    JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, duration_secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs

    def line(self) -> str:
        return (f"so far: compile {self.seconds:.1f} s, persistent cache "
                f"{self.hits} hits / {self.misses} misses")


def _tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _memory(devices) -> str:
    return ", ".join(
        f"{d.id}: peak {d.memory_stats()['peak_bytes_in_use']} B, "
        f"in use {d.memory_stats()['bytes_in_use']} B" for d in devices)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: dense + paged passes on one chip (default); "
                         "4: the four-replica shard_map phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.configs import get_config
    from repro.core.energy import EnergyModel
    from repro.hw import chip_for_device_kind
    from repro.models import abstract_cache, init_params_jit
    from repro.models.model import init_paged_cache
    from repro.serving.pool import params_on_device

    kind = devices[0].device_kind
    hw = chip_for_device_kind(kind)
    print(f"device: {kind} x{len(devices)} ({devices[0].platform}), priced as "
          f"{hw.name}; compile cache {cache_dir}")
    emodel = EnergyModel(hw)
    cfg = get_config(ARCH)
    log = CompileLog()
    used = devices[:args.chips]
    t0 = time.perf_counter()
    replicated = (None if args.chips == 1 else
                  NamedSharding(Mesh(used, ("replica",)), PartitionSpec()))
    params = init_params_jit(cfg, jax.random.PRNGKey(args.seed), replicated)
    jax.block_until_ready(params)
    print(f"params: {ARCH} full width, {cfg.param_dtype}, {_tree_bytes(params)} B "
          f"per chip; {log.line()}; smoke timing (not a benchmark): "
          f"init {time.perf_counter() - t0:.1f} s")
    dense_bytes = _tree_bytes(abstract_cache(cfg, DECODE_BATCH, MAX_SEQ_LEN))
    # the reference runs on the first chip, against that chip's weights
    ref = Reference(cfg, params_on_device(params, used[0] if args.chips > 1 else None),
                    MAX_SEQ_LEN)

    if args.chips == 1:
        n_pages = DECODE_BATCH * MAX_SEQ_LEN // KV_BLOCK + 1
        paged_bytes = _tree_bytes(jax.eval_shape(
            lambda: init_paged_cache(cfg, DECODE_BATCH, n_pages, KV_BLOCK)))
        trace = make_trace(cfg, seed=args.seed)
        for paged, cache_bytes in ((False, dense_bytes), (True, paged_bytes)):
            name = "paged" if paged else "dense"
            print(f"phase {name}: decode batch {DECODE_BATCH}, max_seq_len "
                  f"{MAX_SEQ_LEN}, cache {cache_bytes} B")
            res = serve_and_check(ARCH, params, emodel, trace, ref,
                                  reduced=False, paged=paged)
            st = res["stats"]
            print(f"  engine: {st.decode_steps} decode steps, {st.prefills} "
                  f"prefills, {st.jit_dispatches} dispatches; {log.line()}")
            print(f"  memory: {_memory(used)}")
    else:
        trace = make_trace(cfg, seed=args.seed, aligned=True,
                           prompt_len=(ALIGNED_PROMPT_LEN, ALIGNED_PROMPT_LEN))
        print(f"phase shard_map: {args.chips} replicas x decode batch "
              f"{DECODE_BATCH}, max_seq_len {MAX_SEQ_LEN}, cache {dense_bytes} B "
              f"per replica")
        res = serve_and_check(ARCH, params, emodel, trace, ref, reduced=False,
                              paged=False, replicas=args.chips, devices=used,
                              engine_opts={"batch_layout": "shard_map"})
        st = res["stats"]
        print(f"  engine: {st.fused_decode_calls} fused decode calls, "
              f"{st.shard_map_calls} under shard_map, {st.vmap_fallbacks} vmap "
              f"fallbacks, {st.serial_decode_calls} serial; {log.line()}")
        print(f"  memory: {_memory(used)}")
        _require(st.shard_map_calls > 0, "no fused decode call ran under shard_map")
        _require(st.vmap_fallbacks == 0, f"{st.vmap_fallbacks} vmap fallbacks")
        _require(st.shard_map_calls == st.fused_decode_calls,
                 "a fused decode call ran outside shard_map")

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
