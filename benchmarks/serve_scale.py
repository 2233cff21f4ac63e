"""Event-engine scale gate: 10^6 requests over a 100-replica mixed fleet.

PR-6's event heap made a 16-replica / 10^5-request replay tractable; this
benchmark is the acceptance gate for the next order of magnitude, where
the per-request cost must be O(event-loop bookkeeping), not O(jit
dispatch). The levers under test (``repro.serving.events`` +
``repro.serving.pool``):

    fused admission prefill   same-instant admission ticks defer their
                              ``_jit_prefill`` dispatches; the engine runs
                              one grouped program per (config, params,
                              bucket) and replays per-request accounting
                              byte-identically
    fusion quantum            decode events inside ``[t, t+q)`` share one
                              dispatch even when replica clocks have
                              drifted off exact ties
    pow2 group bucketing      fused program cache stays O(log fleet) on a
                              drifting fleet instead of one trace per
                              group size
    batched replica axis      a fused group of K pools runs as ONE
                              vmap/shard_map-batched program over
                              replica-stacked cache banks instead of a
                              tuple of K traced sub-calls
                              (``batch_replicas``; ``--batched=off``
                              replays the tuple baseline)
    allocation-free loops     request/ledger freelists + ``on_finish``
                              streaming keep the replay memory-flat;
                              round-robin routing is O(1) per arrival

Fleet: 88 gemma-class + 12 minicpm-class replicas (heterogeneous groups
fuse within themselves). Trace: an aligned phase (waves of one request
per replica at one-step cadence — the fused fast path's shape) followed
by a drifted phase (mixed prompt lengths, jittered arrivals — the shape
only the quantum window and pow2 bucketing keep fused).

Asserted:

    scale       all requests complete; double replay streams to the SAME
                sha256 (outputs + ledger stamps + measured joules)
    aligned     >= 80% of decode pool-steps ran through fused dispatches
                on the aligned phase
    dispatch    jit dispatches/request with full fusion strictly below
                the PR-6 dispatch pattern (serial admission prefill,
                exact-tie-only decode fusion) on the same trace, and
                under an absolute ceiling
    quantum     ``fusion_quantum_s=0`` replays byte-identical to the
                exact-tie engine; a positive quantum changes no token
    batched     the vmap-batched fused dispatch streams to the SAME
                sha256 as the tuple-of-K program, and its measured wall
                per fused call beats the tuple's at group sizes >= 8
                (the dispatch-vs-group-size curve lands in the JSON)
    wall        slowest full replay fits the budget
                (REPRO_SCALE_TIME_BUDGET_S, default 3600 s; 0 waives)

Run:  PYTHONPATH=src python -m benchmarks.serve_scale            # full
  or: PYTHONPATH=src python -m benchmarks.serve_scale --smoke    # CI tier
  add --json to write BENCH_serve_scale.json (schema-versioned artefact)
  add --batched=off to replay the tuple-of-K baseline (artefact goes to
  BENCH_serve_scale_unbatched.json so both modes can be diffed)
"""
from __future__ import annotations

import hashlib
import json
import os
import sys
import time

import jax
import numpy as np

from benchmarks.common import h200_model, write_bench_json, write_csv
from repro.configs import reduced_config
from repro.core.traces import TracedRequest
from repro.models import init_params
from repro.serving import (
    ClockSpec,
    Fleet,
    FleetSpec,
    PoolSpec,
    ReplicaSpec,
    clear_program_caches,
)
from repro.serving import spans
from repro.serving.pool import release_request

ARCH_MAIN = "gemma-2b"
ARCH_ALT = "minicpm-2b"
N_MAIN = 88
N_ALT = 12
N_REPLICAS = N_MAIN + N_ALT
BATCH = 8
MAX_SEQ_LEN = 64
CHUNK_TOKENS = 64
PROMPT_LEN = 16
MAX_NEW = 4
WAVE_DT_S = 0.0021                  # ~ one locked-clock decode step
QUANTUM_S = 0.0005                  # ~ a quarter step: re-fuses drift
TRACE_SEED = 23
DISPATCH_CEILING = 1.5              # jit dispatches per request, full run
JSON_PATH = "BENCH_serve_scale.json"
UNBATCHED_JSON_PATH = "BENCH_serve_scale_unbatched.json"
# wall-clock budget for ONE full replay; 0 waives
TIME_BUDGET_S = float(os.environ.get("REPRO_SCALE_TIME_BUDGET_S", "3600"))

_PARAMS_CACHE = {}


def params_for():
    for arch in (ARCH_MAIN, ARCH_ALT):
        if arch not in _PARAMS_CACHE:
            _PARAMS_CACHE[arch] = init_params(
                reduced_config(arch), jax.random.PRNGKey(0))
    return _PARAMS_CACHE


def make_fleet() -> Fleet:
    archs = [ARCH_MAIN] * N_MAIN + [ARCH_ALT] * N_ALT
    spec = FleetSpec(
        replicas=tuple(
            ReplicaSpec(name=f"r{i:03d}", arch=arch,
                        clock=ClockSpec(mode="lock"),
                        decode=PoolSpec(batch=BATCH),
                        max_seq_len=MAX_SEQ_LEN,
                        prefill_chunk_tokens=CHUNK_TOKENS)
            for i, arch in enumerate(archs)),
        router="rr",                # O(1) per arrival; JSQ would be O(N)
    )
    return Fleet.from_spec(spec, emodel=h200_model(), params_for=params_for())


def aligned_trace(n_requests: int, *, t0: float = 0.0):
    """Waves of one identical prompt per replica at one-step cadence —
    every fused path (admission + decode) at full coverage. The prompt
    array is SHARED across requests: a million-request trace must not
    hold a million numpy buffers. Partial waves are dropped; callers
    surface the count (see serve_events.wave_trace)."""
    rng = np.random.default_rng(TRACE_SEED)
    prompt = rng.integers(1, 100, PROMPT_LEN).astype(np.int32)
    n_waves = n_requests // N_REPLICAS
    trace = [
        TracedRequest(arrival_s=t0 + w * WAVE_DT_S, prompt=prompt,
                      max_new_tokens=MAX_NEW, bucket="mixed")
        for w in range(n_waves) for _ in range(N_REPLICAS)
    ]
    return trace, n_requests - len(trace)


def drifted_trace(n_requests: int, *, t0: float = 0.0):
    """Mixed prompt lengths + jittered arrivals: replica clocks drift off
    exact ties, so only the fusion quantum and pow2 group bucketing keep
    dispatches shared. Prompts come from a small shared pool of arrays."""
    rng = np.random.default_rng(TRACE_SEED + 1)
    pool = [rng.integers(1, 100, int(n)).astype(np.int32)
            for n in rng.integers(8, 25, 32)]
    trace = []
    for i in range(n_requests):
        jitter = float(rng.uniform(0.0, 0.3 * WAVE_DT_S))
        trace.append(TracedRequest(
            arrival_s=t0 + (i // N_REPLICAS) * WAVE_DT_S + jitter,
            prompt=pool[int(rng.integers(0, len(pool)))],
            max_new_tokens=MAX_NEW, bucket="mixed"))
    return trace


def scale_trace(n_requests: int):
    """Aligned phase then drifted phase, half each."""
    n_aligned = n_requests // 2
    a, dropped = aligned_trace(n_aligned)
    t0 = (len(a) // N_REPLICAS + 2) * WAVE_DT_S if a else 0.0
    d = drifted_trace(n_requests - len(a), t0=t0)
    return a + d, dropped


class StreamHash:
    """Streaming replay fingerprint + latency accumulator: hashes every
    finished request in completion order and releases it back to the
    request freelist, so the replay holds O(in-flight) requests."""

    def __init__(self):
        self._h = hashlib.sha256()
        self.completed = 0
        self.ttft = []
        self.e2e = []

    def __call__(self, req):
        led = req.ledger
        self._h.update(json.dumps(
            [req.replica, req.uid, req.output, led.arrival_s,
             led.admitted_s, led.first_token_s, led.finish_s]).encode())
        self.completed += 1
        self.ttft.append(led.first_token_s - led.arrival_s)
        self.e2e.append(led.finish_s - led.arrival_s)
        release_request(req)

    def digest(self, fleet) -> str:
        self._h.update(json.dumps(fleet.measured_energy_j(),
                                  sort_keys=True).encode())
        return self._h.hexdigest()


def replay(trace, **engine_opts):
    """One streamed replay; returns (metrics, sha256, wall_s)."""
    fleet = make_fleet()
    stream = StreamHash()
    opts = {"on_finish": stream, **engine_opts}
    t0 = time.perf_counter()
    fleet.run_trace(trace, max_steps=1_000_000_000, engine_opts=opts)
    wall_s = time.perf_counter() - t0
    st = fleet.last_engine_stats
    ttft = np.asarray(stream.ttft)
    metrics = {
        "completed": stream.completed,
        "requests": len(trace),
        "replicas": N_REPLICAS,
        "decode_steps": st.decode_steps,
        "jit_dispatches": st.jit_dispatches,
        "dispatches_per_request": st.jit_dispatches / max(len(trace), 1),
        "fused_decode_coverage": st.fused_decode_coverage,
        "fused_prefill_coverage": st.fused_prefill_coverage,
        "batched_decode_calls": st.batched_decode_calls,
        "bank_rebuilds": st.bank_rebuilds,
        "peak_heap": st.peak_heap,
        "events": st.events,
        "total_j": fleet.total_energy_j(),
        "p50_ttft_s": float(np.percentile(ttft, 50)) if len(ttft) else None,
        "p99_ttft_s": float(np.percentile(ttft, 99)) if len(ttft) else None,
        "engine_stats": st.as_dict(),
    }
    return metrics, stream.digest(fleet), wall_s


def fused_walls(records) -> dict:
    """Wall seconds of each fused decode group, from the span recorder's
    ``decode.fused`` records, by padded group size as a string: size ->
    [calls, seconds]. A group's wall is its dispatch and its members'
    tokens reaching the host: the span less its ``decode.account``
    children."""
    account: dict = {}
    for name, t0, t1, parent, _ in records:
        if name == "decode.account" and parent >= 0:
            account[parent] = account.get(parent, 0) + t1 - t0
    out: dict = {}
    for i, (name, t0, t1, _, size) in enumerate(records):
        if name == "decode.fused":
            ent = out.setdefault(str(size), [0, 0.0])
            ent[0] += 1
            ent[1] += (t1 - t0 - account.get(i, 0)) / 1e9
    return out


def dispatch_curve(smoke: bool):
    """Measured wall seconds inside fused decode dispatches vs group size,
    batched vs tuple program, on the aligned trace (full fused coverage),
    read from the span recorder. ``clear_program_caches()`` between points
    so every point pays its own compiles — the curve is (compile +
    dispatch) per fused call, the cost a replay actually sees the first
    time it meets a group size."""
    sweep = (4, 8, 32) if smoke else (4, 8, 16, 32, 64)
    n = 1_500 if smoke else 20_000
    trace, _ = aligned_trace(n)
    curve: dict = {}
    for g in sweep:
        for mode, flag in (("batched", True), ("tuple", False)):
            clear_program_caches()
            fleet = make_fleet()
            spans.clear()
            spans.enable()
            try:
                fleet.run_trace(trace, max_steps=1_000_000_000, engine_opts={
                    "fusion_quantum_s": QUANTUM_S, "max_fused_group": g,
                    "batch_replicas": flag})
            finally:
                spans.disable()
            by_size = fused_walls(spans.records())
            spans.clear()
            calls = sum(v[0] for v in by_size.values())
            secs = sum(v[1] for v in by_size.values())
            curve.setdefault(str(g), {})[mode] = {
                "fused_calls": calls,
                "dispatch_wall_s": secs,
                "us_per_fused_call": 1e6 * secs / max(calls, 1),
                "by_size": by_size,
            }
    clear_program_caches()
    return curve


def run(smoke: bool = False, write_json: bool = False, batched: bool = True):
    """Harness contract: yields (name, us_per_call, derived) rows; raises
    on any violated completion/determinism/coverage/dispatch assertion."""
    if smoke:
        n_scale, n_aligned, n_compare = 4_000, 2_000, 1_000
    else:
        n_scale, n_aligned, n_compare = 1_000_000, 50_000, 10_000
    # every replay below runs in the requested engine mode; the batched
    # identity section crosses over to the OTHER mode to pin the sha
    base = {"batch_replicas": batched}

    out_rows = []
    violations = []

    # ---- the scale run: mixed trace, streamed, double replay -------------
    trace, dropped = scale_trace(n_scale)
    if dropped:
        print(f"serve_scale: dropped {dropped} requests to whole waves",
              file=sys.stderr)
    first, sha_a, wall_a = replay(trace, fusion_quantum_s=QUANTUM_S, **base)
    again, sha_b, wall_b = replay(trace, fusion_quantum_s=QUANTUM_S, **base)
    out_rows.append((
        "serve_scale/replay",
        1e6 * wall_a / max(len(trace), 1),
        f"requests={len(trace)};dropped={dropped};replicas={N_REPLICAS};"
        f"dispatches_per_request={first['dispatches_per_request']:.3f};"
        f"peak_heap={first['peak_heap']};total_j={first['total_j']:.1f};"
        f"wall_s={wall_a:.1f}",
    ))
    if first["completed"] != len(trace):
        violations.append(
            f"scale: {first['completed']}/{len(trace)} completed")
    identical = sha_a == sha_b and first == again
    if not identical:
        violations.append("scale replay NOT byte-identical across runs")
    out_rows.append((
        "serve_scale/determinism", 0.0,
        f"byte_identical={identical};sha={sha_a[:16]}",
    ))
    # prefix sharing defaults off: this fleet must be untouched by it
    es = first["engine_stats"]
    if (es["prefix_hits"], es["prefix_cow_splits"],
            es["saved_prefill_j"]) != (0, 0, 0.0):
        violations.append(
            f"prefix sharing leaked into a sharing-off fleet: "
            f"hits={es['prefix_hits']} cow={es['prefix_cow_splits']} "
            f"saved_j={es['saved_prefill_j']}")
    if first["dispatches_per_request"] >= DISPATCH_CEILING:
        violations.append(
            f"{first['dispatches_per_request']:.3f} jit dispatches/request "
            f"(ceiling {DISPATCH_CEILING})")

    # ---- aligned phase: fused coverage ------------------------------------
    atrace, _ = aligned_trace(n_aligned)
    amet, _, _ = replay(atrace, **base)
    if amet["fused_decode_coverage"] < 0.80:
        violations.append(
            f"aligned fused decode coverage "
            f"{100 * amet['fused_decode_coverage']:.1f}% < 80%")
    out_rows.append((
        "serve_scale/aligned_coverage", 0.0,
        f"fused_decode_pct={100 * amet['fused_decode_coverage']:.1f};"
        f"fused_prefill_pct={100 * amet['fused_prefill_coverage']:.1f}",
    ))

    # ---- dispatch count: full fusion vs the PR-6 dispatch pattern ---------
    ctrace, _ = scale_trace(n_compare)
    fused_m, fused_sha, _ = replay(ctrace, fusion_quantum_s=QUANTUM_S, **base)
    serial_m, _, _ = replay(ctrace, fuse_prefill=False, **base)
    if not fused_m["jit_dispatches"] < serial_m["jit_dispatches"]:
        violations.append(
            f"fusion did not reduce dispatches: "
            f"{fused_m['jit_dispatches']} vs {serial_m['jit_dispatches']}")
    out_rows.append((
        "serve_scale/dispatches_vs_serial", 0.0,
        f"fused={fused_m['jit_dispatches']};"
        f"serial={serial_m['jit_dispatches']};"
        f"saved_pct={100 * (1 - fused_m['jit_dispatches'] / max(serial_m['jit_dispatches'], 1)):.1f}",
    ))

    # ---- batched replica axis: cross-mode byte identity -------------------
    # the tentpole gate: ONE vmap-batched program over replica-stacked
    # cache banks streams to the SAME sha256 as the tuple of K traced
    # sub-calls on the same trace
    cross_m, cross_sha, _ = replay(ctrace, fusion_quantum_s=QUANTUM_S,
                                   batch_replicas=not batched)
    if cross_sha != fused_sha:
        violations.append(
            "batched fused dispatch NOT byte-identical to the tuple-of-K "
            "program")
    bat_m = fused_m if batched else cross_m
    if bat_m["batched_decode_calls"] == 0:
        violations.append("batched replica axis was never exercised")
    out_rows.append((
        "serve_scale/batched_identity", 0.0,
        f"byte_identical={cross_sha == fused_sha};"
        f"batched_decode_calls={bat_m['batched_decode_calls']};"
        f"bank_rebuilds={bat_m['bank_rebuilds']}",
    ))

    # ---- quantum semantics ------------------------------------------------
    q0_m, q0_sha, _ = replay(ctrace, fusion_quantum_s=0.0, **base)
    exact_m, exact_sha, _ = replay(ctrace, **base)
    if q0_sha != exact_sha:
        violations.append("quantum=0 NOT byte-identical to exact-tie engine")
    if fused_sha != q0_sha:
        # the quantum only regroups dispatches: outputs/stamps/joules are
        # invariant, so even the positive-quantum replay matches
        violations.append("positive quantum changed the replay fingerprint")
    out_rows.append((
        "serve_scale/quantum", 0.0,
        f"q0_identical={q0_sha == exact_sha};"
        f"q_invariant={fused_sha == q0_sha};quantum_s={QUANTUM_S}",
    ))

    # ---- dispatch wall vs group size: batched must win at >= 8 ------------
    # only in the primary (batched) invocation: the curve already measures
    # BOTH modes per point, so the opt-out artefact need not repeat it
    curve = {}
    if batched:
        curve = dispatch_curve(smoke)
        for g, point in sorted(curve.items(), key=lambda kv: int(kv[0])):
            b = point["batched"]["us_per_fused_call"]
            t = point["tuple"]["us_per_fused_call"]
            if int(g) >= 8 and not b < t:
                violations.append(
                    f"batched dispatch slower at group size {g}: "
                    f"{b:.0f}us vs tuple {t:.0f}us per fused call")
            out_rows.append((
                f"serve_scale/dispatch_curve/g{g}", b,
                f"batched_us_per_call={b:.0f};tuple_us_per_call={t:.0f};"
                f"speedup={t / max(b, 1e-9):.2f}x;"
                f"calls={point['batched']['fused_calls']}",
            ))

    # ---- wall budget ------------------------------------------------------
    slowest = max(wall_a, wall_b)
    if TIME_BUDGET_S > 0:
        if slowest > TIME_BUDGET_S:
            violations.append(
                f"a replay took {slowest:.1f}s "
                f"(> {TIME_BUDGET_S:.0f}s budget)")
        out_rows.append((
            "serve_scale/wall_time", 0.0,
            f"slowest_replay_s={slowest:.1f};budget_s={TIME_BUDGET_S:.0f}",
        ))

    results = {"scale": first, "scale_sha": sha_a, "aligned": amet,
               "dispatch": {"fused": fused_m["jit_dispatches"],
                            "serial": serial_m["jit_dispatches"]},
               "batched": {"mode": "batched" if batched else "tuple",
                           "cross_mode_identical": cross_sha == fused_sha,
                           "batched_decode_calls":
                               bat_m["batched_decode_calls"],
                           "bank_rebuilds": bat_m["bank_rebuilds"]},
               "dispatch_curve": curve,
               "wall_s": [wall_a, wall_b]}
    write_csv("serve_scale", ["metric", "value"],
              [[k, v] for k, v in first.items() if k != "engine_stats"]
              + [["aligned_fused_decode_coverage",
                  amet["fused_decode_coverage"]],
                 ["dispatch_fused", fused_m["jit_dispatches"]],
                 ["dispatch_serial", serial_m["jit_dispatches"]]])
    if write_json:
        json_path = JSON_PATH if batched else UNBATCHED_JSON_PATH
        write_bench_json(
            "serve_scale", results, smoke=smoke, path=json_path,
            trace={"n": len(trace), "n_requested": n_scale,
                   "dropped": dropped, "shape": "aligned+drifted",
                   "wave_dt_s": WAVE_DT_S, "quantum_s": QUANTUM_S,
                   "prompt_len": PROMPT_LEN, "max_new": MAX_NEW,
                   "seed": TRACE_SEED, "batched": batched},
        )
        out_rows.append(("serve_scale/json", 0.0, f"wrote={json_path}"))
    if violations:
        raise RuntimeError("; ".join(violations))
    return out_rows


def main():
    argv = sys.argv[1:]
    smoke = "--smoke" in argv
    write_json = "--json" in argv
    batched = True
    for a in argv:
        if a.startswith("--batched"):
            val = a.partition("=")[2] or "on"
            if val not in ("on", "off"):
                print(f"--batched takes on|off, got {val!r}")
                sys.exit(2)
            batched = val == "on"
    ok = True
    try:
        for name, us, derived in run(smoke=smoke, write_json=write_json,
                                     batched=batched):
            print(f"{name},{us:.1f},{derived}")
    except RuntimeError as e:
        print(f"serve_scale checks VIOLATED: {e}")
        ok = False
    print("serve_scale checks:", "OK" if ok else "VIOLATED")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
