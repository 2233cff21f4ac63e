"""Batched replica axis: the vmap/shard_map-batched fused dispatch must be
byte-identical to the tuple-of-K fused program AND the serial engine —
tokens, every ledger stamp, modelled + measured joules — on aligned,
drifted-quantum, and mixed-arch traces. Plus the identity/cache bugfix
satellites: stable params tokens (no id() recycling cross-talk), capped
program caches + ``clear_program_caches``, the id()-free clock-sharing
guard, and the ``engine_opts`` spec plumbing."""
import dataclasses
import gc
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from _propcheck import given, settings, strategies

from repro.configs import reduced_config
from repro.core import EnergyModel, VirtualClock
from repro.core.latency import summarize_latency
from repro.core.traces import TracedRequest
from repro.hw import H200_SXM
from repro.models import init_params
from repro.serving import (
    ClockSpec,
    EventDrivenFleet,
    Fleet,
    FleetSpec,
    PoolSpec,
    ReplicaSpec,
    clear_program_caches,
    params_token_for,
)
from repro.serving import events as events_mod
from repro.serving import pool as pool_mod
from repro.serving import spans
from repro.serving.fleet import Replica

ARCH = "gemma-2b"
ALT = "mamba2-780m"            # different family: per-arch grouping


_SETUP_CACHE: dict = {}


def _setup_cached():
    if not _SETUP_CACHE:
        params = {}
        for arch in (ARCH, ALT):
            params[arch] = init_params(reduced_config(arch),
                                       jax.random.PRNGKey(0))
        _SETUP_CACHE["v"] = params
    return _SETUP_CACHE["v"]


@pytest.fixture(scope="module")
def setup():
    return _setup_cached()


def _req(prompt_len, arrival_s, max_new, seed=0, temp=0.0):
    rng = np.random.default_rng(seed + prompt_len)
    return TracedRequest(
        arrival_s=arrival_s,
        prompt=rng.integers(1, 100, prompt_len).astype(np.int32),
        max_new_tokens=max_new, bucket="mixed", temperature=temp)


def _fleet(params, n=4, archs=None):
    archs = archs or [ARCH] * n
    spec = FleetSpec(
        replicas=tuple(
            ReplicaSpec(name=f"r{i}", arch=a, clock=ClockSpec(mode="lock"),
                        decode=PoolSpec(batch=2), max_seq_len=64,
                        prefill_chunk_tokens=64)
            for i, a in enumerate(archs)),
        router="jsq")
    return Fleet.from_spec(spec, emodel=EnergyModel(H200_SXM),
                           params_for=params)


def _blob(done, fleet):
    done = sorted(done, key=lambda r: r.uid)
    return json.dumps({
        "outputs": [r.output for r in done],
        "stamps": [[r.ledger.arrival_s, r.ledger.admitted_s,
                    r.ledger.first_token_s, r.ledger.finish_s] for r in done],
        "lat": dataclasses.asdict(summarize_latency(done)),
        "modelled": fleet.total_energy_j(),
        "measured": fleet.measured_energy_j(),
    }, sort_keys=True)


def _run(params, trace, n=4, archs=None, **opts):
    fleet = _fleet(params, n=n, archs=archs)
    opts.setdefault("fast_path_min", 2)
    done = fleet.run_trace(trace, engine_opts=opts)
    assert len(done) == len(trace)
    return fleet, _blob(done, fleet)


# the three engine modes every identity test compares: the batched replica
# axis, the PR-7 tuple-of-K fused baseline, and the fully serial engine
MODES = (
    ("batched", {"batch_replicas": True}),
    ("tuple", {"batch_replicas": False}),
    ("serial", {"batch_replicas": False, "fast_path_min": 99}),
)


def _aligned_trace(n=12, max_new=6):
    """Identical prompt lengths, one burst: replicas stay step-aligned, the
    widest grouping. Mixed temperatures keep the RNG-split order
    load-bearing."""
    return [_req(16, 0.0, max_new, seed=10 + i,
                 temp=0.7 if i % 3 == 0 else 0.0) for i in range(n)]


def _drifted_trace(n=10, max_new=8):
    """Staggered sub-step arrivals: exact ties never happen, the fusion
    quantum is what re-fuses the drifted steps into variable-size groups."""
    return [_req(16, 1e-4 * i, max_new, seed=30 + i,
                 temp=0.7 if i % 4 == 0 else 0.0) for i in range(n)]


class TestBatchedByteIdentity:
    def test_aligned_burst(self, setup):
        """The tentpole gate: ONE vmap-batched program over replica-stacked
        buffers changes nothing observable vs the tuple-of-K fused program
        vs the serial engine."""
        blobs, stats = {}, {}
        for mode, opts in MODES:
            fleet, blobs[mode] = _run(setup, _aligned_trace(), **opts)
            stats[mode] = fleet.last_engine_stats
        assert blobs["batched"] == blobs["tuple"] == blobs["serial"]
        assert stats["batched"].batched_decode_calls > 0
        assert stats["batched"].fused_decode_calls == \
            stats["tuple"].fused_decode_calls
        assert stats["tuple"].batched_decode_calls == 0
        assert stats["serial"].batched_decode_calls == 0

    def test_drifted_quantum(self, setup):
        """Same identity under quantum re-fusion (variable group sizes,
        pow2 padding in play on a 6-replica fleet)."""
        blobs = {}
        for mode, opts in MODES:
            fleet, blobs[mode] = _run(setup, _drifted_trace(), n=6,
                                      fusion_quantum_s=0.5, **opts)
            if mode == "batched":
                st = fleet.last_engine_stats
                assert st.batched_decode_calls > 0
                assert st.pad_waste > 0      # pow2 padding exercised
        assert blobs["batched"] == blobs["tuple"] == blobs["serial"]

    def test_mixed_arch_fleet(self, setup):
        """Mixed-arch fleets group per decode signature: each arch's group
        batches independently and the replay stays byte-identical."""
        archs = [ARCH, ARCH, ALT, ALT]
        blobs = {}
        for mode, opts in MODES:
            fleet, blobs[mode] = _run(setup, _aligned_trace(n=8), n=4,
                                      archs=archs, **opts)
            if mode == "batched":
                assert fleet.last_engine_stats.batched_decode_calls > 0
        assert blobs["batched"] == blobs["tuple"] == blobs["serial"]

    def test_shard_map_layout_single_device_identical(self, setup):
        """``batch_layout="shard_map"`` on a 1-device host falls back to
        vmap — the flag must never change a byte."""
        _, vmap_blob = _run(setup, _aligned_trace(), batch_replicas=True)
        fleet, shard_blob = _run(setup, _aligned_trace(),
                                 batch_replicas=True,
                                 batch_layout="shard_map")
        assert shard_blob == vmap_blob
        st = fleet.last_engine_stats
        assert st.batched_decode_calls > 0
        # the fallback is counted, never silent
        assert st.vmap_fallbacks == st.batched_decode_calls
        assert st.shard_map_calls == 0

    @pytest.mark.slow
    def test_shard_map_multi_device_identical(self):
        """On a forced 2-device host the shard_map layout actually shards
        the replica axis over the mesh — still byte-identical to vmap
        (replicas never communicate). Subprocess: XLA device count is
        process-global."""
        code = (
            "import dataclasses, json\n"
            "import jax, numpy as np\n"
            "assert len(jax.devices()) == 2, jax.devices()\n"
            "from repro.configs import reduced_config\n"
            "from repro.core import EnergyModel\n"
            "from repro.core.traces import TracedRequest\n"
            "from repro.hw import H200_SXM\n"
            "from repro.models import init_params\n"
            "from repro.serving import (ClockSpec, Fleet, FleetSpec,"
            " PoolSpec, ReplicaSpec)\n"
            "cfg = reduced_config('gemma-2b')\n"
            "params = {'gemma-2b': init_params(cfg, jax.random.PRNGKey(0))}\n"
            "def req(i):\n"
            "    rng = np.random.default_rng(10 + i + 16)\n"
            "    return TracedRequest(arrival_s=0.0,\n"
            "        prompt=rng.integers(1, 100, 16).astype(np.int32),\n"
            "        max_new_tokens=4, bucket='mixed',\n"
            "        temperature=0.7 if i % 3 == 0 else 0.0)\n"
            "def run(layout):\n"
            "    spec = FleetSpec(replicas=tuple(\n"
            "        ReplicaSpec(name=f'r{i}', arch='gemma-2b',\n"
            "                    clock=ClockSpec(mode='lock'),\n"
            "                    decode=PoolSpec(batch=2), max_seq_len=64,\n"
            "                    prefill_chunk_tokens=64)\n"
            "        for i in range(4)), router='jsq')\n"
            "    fleet = Fleet.from_spec(spec, emodel=EnergyModel(H200_SXM),\n"
            "                            params_for=params)\n"
            "    done = fleet.run_trace([req(i) for i in range(8)],\n"
            "        engine_opts={'fast_path_min': 2, 'batch_layout': layout})\n"
            "    st = fleet.last_engine_stats\n"
            "    rows = [[r.output, r.ledger.finish_s, r.energy_j]\n"
            "            for r in sorted(done, key=lambda r: r.uid)]\n"
            "    return json.dumps(rows), st.batched_decode_calls\n"
            "v, vc = run('vmap')\n"
            "s, sc = run('shard_map')\n"
            "assert vc > 0 and sc > 0, (vc, sc)\n"
            "assert v == s\n"
            "print('OK')\n"
        )
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=2")
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src"),
             env.get("PYTHONPATH", "")])
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout


_BATCH_BASELINES: dict = {}


@settings(max_examples=6, deadline=None)
@given(seed=strategies.integers(min_value=0, max_value=5),
       q=strategies.floats(min_value=0.0, max_value=0.25))
def test_property_batched_equals_serial(seed, q):
    """Property: under ANY seed/quantum the batched replica axis replays
    the serial engine's token streams and stamps exactly. (Module-level:
    the propcheck fallback can't thread fixtures through ``@given``.)"""
    params = _setup_cached()
    rng = np.random.default_rng(seed)
    trace = [_req(int(rng.integers(4, 20)), float(rng.uniform(0, 0.005)),
                  int(rng.integers(2, 6)), seed=seed * 100 + i,
                  temp=0.7 if i % 3 == 0 else 0.0)
             for i in range(8)]
    base = _BATCH_BASELINES.get(seed)
    if base is None:
        _, base = _run(params, trace, n=3, fast_path_min=99,
                       batch_replicas=False)
        _BATCH_BASELINES[seed] = base
    _, blob = _run(params, trace, n=3, fusion_quantum_s=float(q),
                   batch_replicas=True)
    assert blob == base


class TestBatchedStats:
    def test_pad_waste_consistent_across_modes(self, setup):
        """Pad accounting is a property of the grouping, not the program:
        batched and tuple replays of the same trace report identical
        fused-call and pad-waste counters, and the pow2 bound holds."""
        sts = {}
        for mode, opts in MODES[:2]:
            fleet, _ = _run(setup, _drifted_trace(), n=6,
                            fusion_quantum_s=0.5, **opts)
            sts[mode] = fleet.last_engine_stats
        b, t = sts["batched"], sts["tuple"]
        assert b.fused_decode_calls == t.fused_decode_calls
        assert b.pad_waste == t.pad_waste
        assert b.batched_decode_calls == b.fused_decode_calls
        # every fused call pads to pow2: waste < group size per call
        assert b.pad_waste < 6 * b.fused_decode_calls
        assert b.bank_rebuilds <= b.batched_decode_calls

    def test_dispatch_wall_clock_ledger(self, setup):
        """The span recorder's ``decode.fused`` records time each fused
        group for the dispatch-vs-group-size curve: one record per fused
        dispatch, carrying its pow2-padded group size, with every member's
        ``decode.sync``/``decode.account`` nested inside it."""
        spans.clear()
        spans.enable()
        try:
            fleet, _ = _run(setup, _aligned_trace())
        finally:
            spans.disable()
        recs = list(spans.records())
        spans.clear()
        st = fleet.last_engine_stats
        fused = [i for i, r in enumerate(recs) if r[0] == "decode.fused"]
        assert fused, "no fused group recorded"
        assert len(fused) == st.fused_decode_calls
        assert all(recs[i][2] >= recs[i][1] for i in fused)
        sizes = [recs[i][4] for i in fused]
        assert all(k > 0 and (k & (k - 1)) == 0 for k in sizes)
        assert st.pad_waste == sum(sizes) - sum(
            r[0] == "decode.sync" and r[3] in fused for r in recs)
        for i in fused:
            kids = [r for r in recs if r[3] == i]
            assert {r[0] for r in kids} == {"decode.sync", "decode.account"}
            assert all(recs[i][1] <= r[1] and r[2] <= recs[i][2] for r in kids)
        json.dumps(st.as_dict())

    def test_batched_keys_reuse_decode_kind(self, setup):
        """The batched fast path keeps the ``("decode", sig, p2)`` fused
        cache shape (pow2 sizes, O(log fleet) entries) so cache-bucketing
        invariants hold across engine modes."""
        fleet = _fleet(setup, n=4)
        eng = EventDrivenFleet(fleet, fast_path_min=2)
        eng.run(_aligned_trace())
        decode_keys = [k for k in eng._fused_cache if k[0] == "decode"]
        assert decode_keys
        assert all(s & (s - 1) == 0 for _, _, s in decode_keys)


class TestParamsToken:
    def test_token_is_stable_and_distinct(self):
        a, b = {"w": np.zeros(2)}, {"w": np.zeros(2)}
        ta, tb = params_token_for(a), params_token_for(b)
        assert ta != tb                     # equal contents, distinct weights
        assert params_token_for(a) == ta    # stable across calls
        assert params_token_for(b) == tb

    def test_recycled_id_never_reuses_a_token(self):
        """The id() bug this replaces: a freed params dict's id can be
        recycled onto new weights. The registry's identity guard hands the
        newcomer a FRESH token even when ``id()`` collides."""
        seen = set()
        for _ in range(50):                 # allocator loves recycling these
            p = {"w": np.zeros(1)}
            tok = params_token_for(p)
            assert tok not in seen, "token reused across distinct params"
            seen.add(tok)
            del p

    def test_registry_is_capped(self):
        keep = [{"i": i} for i in range(pool_mod._PARAMS_TOKEN_CAP + 16)]
        for p in keep:
            params_token_for(p)
        assert len(pool_mod._PARAMS_TOKENS) <= pool_mod._PARAMS_TOKEN_CAP
        # eviction = fresh token on return, never a stale one
        t0 = params_token_for(keep[0])
        assert t0 == params_token_for(keep[0])

    def test_freed_fleet_no_cache_cross_talk(self, setup):
        """Regression for the fused-dispatch signature bug: run fleet A,
        free it, build fleet B with DIFFERENT weights at whatever addresses
        the allocator hands out — B's fused replay must match B's own
        serial replay, never resurrect A's grouping or programs."""
        trace = _aligned_trace(n=8)
        fleet_a, _ = _run(setup, trace)
        del fleet_a
        gc.collect()
        params_b = {ARCH: init_params(reduced_config(ARCH),
                                      jax.random.PRNGKey(7))}
        fleet_b, fused = _run(params_b, trace)
        assert fleet_b.last_engine_stats.batched_decode_calls > 0
        _, serial = _run(params_b, trace, fast_path_min=99,
                         batch_replicas=False)
        assert fused == serial

    def test_pools_carry_the_token(self, setup):
        fleet = _fleet(setup, n=2)
        toks = {p.params_token
                for r in fleet.replicas for p in r.pools().values()}
        assert len(toks) == 1               # same weights -> same token
        assert toks == {params_token_for(setup[ARCH])}


class TestProgramCaches:
    def test_jit_cache_is_capped_lru(self):
        clear_program_caches()
        for i in range(pool_mod._JIT_CACHE_CAP + 32):
            pool_mod._cached(("synthetic", i), lambda: object())
        assert len(pool_mod._JIT_CACHE) <= pool_mod._JIT_CACHE_CAP
        # LRU: the newest synthetic key survived, the oldest was evicted
        assert ("synthetic", pool_mod._JIT_CACHE_CAP + 31) in pool_mod._JIT_CACHE
        assert ("synthetic", 0) not in pool_mod._JIT_CACHE
        clear_program_caches()

    def test_program_cache_is_capped_lru(self):
        clear_program_caches()
        for i in range(events_mod._PROGRAM_CACHE_CAP + 32):
            events_mod._program(("synthetic", i), lambda: object())
        assert len(events_mod._PROGRAM_CACHE) <= events_mod._PROGRAM_CACHE_CAP
        clear_program_caches()
        assert not events_mod._PROGRAM_CACHE
        assert not pool_mod._JIT_CACHE

    def test_clear_between_replays_changes_nothing(self, setup):
        """The benchmark-sweep contract: clearing the process-wide caches
        between replays only costs recompiles — the replay bytes are
        unchanged and live engines never break."""
        trace = _aligned_trace(n=8)
        _, first = _run(setup, trace)
        clear_program_caches()
        _, second = _run(setup, trace)
        assert first == second


class TestClockGuard:
    def _replica(self, params, name, clock, prefill_clock=None):
        return Replica(reduced_config(ARCH), params[ARCH], name=name,
                       max_seq_len=64, decode_batch=2, clock=clock,
                       prefill_clock=prefill_clock)

    def test_fleet_wide_shared_clock_ok(self, setup):
        c = VirtualClock()
        Fleet([self._replica(setup, "a", c), self._replica(setup, "b", c)])

    def test_per_replica_private_clocks_ok(self, setup):
        Fleet([self._replica(setup, "a", VirtualClock(), VirtualClock()),
               self._replica(setup, "b", VirtualClock(), VirtualClock())])

    def test_partial_sharing_rejected_with_names(self, setup):
        """A clock shared by SOME replicas but not all lets one replica's
        steps silently advance another's timeline — reject, naming the
        offenders."""
        shared = VirtualClock()
        with pytest.raises(ValueError, match="partially shared.*'a'.*'b'"):
            Fleet([self._replica(setup, "a", shared),
                   self._replica(setup, "b", shared),
                   self._replica(setup, "c", VirtualClock())])

    def test_split_prefill_decode_clocks_ok(self, setup):
        """The event engine's overlap layout — each replica owns TWO
        private clocks — must pass the guard."""
        reps = [self._replica(setup, n, VirtualClock(), VirtualClock())
                for n in ("a", "b", "c")]
        fleet = Fleet(reps)
        assert fleet.virtual

    def test_wall_fleet_needs_one_clock(self, setup):
        import time as _time
        Fleet([self._replica(setup, "a", _time.perf_counter),
               self._replica(setup, "b", _time.perf_counter)])
        with pytest.raises(ValueError, match="share one clock"):
            Fleet([self._replica(setup, "a", _time.perf_counter),
                   self._replica(setup, "b", lambda: 0.0)])


class TestEngineOptsSpec:
    def test_spec_roundtrip_and_validation(self):
        spec = FleetSpec(
            replicas=(ReplicaSpec(name="a", arch=ARCH, max_seq_len=64,
                                  clock=ClockSpec(mode="lock")),),
            engine_opts={"batch_replicas": False, "fusion_quantum_s": 0.1})
        assert FleetSpec.from_json(spec.to_json()) == spec
        with pytest.raises(ValueError, match="unknown FleetSpec.engine_opts"):
            FleetSpec(replicas=spec.replicas,
                      engine_opts={"turbo_mode": True})
        with pytest.raises(ValueError, match="JSON"):
            FleetSpec(replicas=spec.replicas,
                      engine_opts={"batch_replicas": object()})

    def test_invalid_layout_fails_loudly(self, setup):
        with pytest.raises(ValueError, match="batch_layout"):
            EventDrivenFleet(_fleet(setup, n=1), batch_layout="pmap")

    def test_spec_opts_pin_the_mode_and_calls_override(self, setup):
        """FleetSpec.engine_opts land on the fleet and gate run_trace;
        per-call engine_opts override key-by-key."""
        spec = FleetSpec(
            replicas=tuple(
                ReplicaSpec(name=f"r{i}", arch=ARCH, max_seq_len=64,
                            clock=ClockSpec(mode="lock"),
                            decode=PoolSpec(batch=2),
                            prefill_chunk_tokens=64)
                for i in range(3)),
            engine_opts={"batch_replicas": False, "fast_path_min": 2})
        trace = _aligned_trace(n=6, max_new=4)

        fleet = Fleet.from_spec(spec, emodel=EnergyModel(H200_SXM),
                                params_for=setup)
        fleet.run_trace(trace)
        st = fleet.last_engine_stats
        assert st.fused_decode_calls > 0
        assert st.batched_decode_calls == 0      # spec pinned the opt-out

        fleet = Fleet.from_spec(spec, emodel=EnergyModel(H200_SXM),
                                params_for=setup)
        fleet.run_trace(trace, engine_opts={"batch_replicas": True})
        assert fleet.last_engine_stats.batched_decode_calls > 0
