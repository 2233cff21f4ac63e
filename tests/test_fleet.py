"""Fleet API: declarative specs (validation + JSON round-trip), pluggable
routers (determinism, policy behaviour), single-replica equivalence with
``Cluster``, drain/power-down gating, and the queue-delay latency summary."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core import EnergyModel, VirtualClock
from repro.core.latency import LatencyLedger, summarize_latency
from repro.core.traces import generate_trace
from repro.hw import H200_SXM
from repro.models import init_params
from repro.serving import (
    ClockSpec,
    Cluster,
    Fleet,
    FleetSpec,
    PoolSpec,
    ReplicaSpec,
    ServingEngine,
    make_router,
)

ARCH = "gemma-2b"
ALT = "mamba2-780m"          # different family: heterogeneous-fleet tests


@pytest.fixture(scope="module")
def setup():
    params = {}
    for arch in (ARCH, ALT):
        params[arch] = init_params(reduced_config(arch), jax.random.PRNGKey(0))
    return params


def _rspec(name, arch=ARCH, mode="lock", batch=2, **clock_kw):
    return ReplicaSpec(
        name=name, arch=arch,
        clock=ClockSpec(mode=mode, **clock_kw),
        decode=PoolSpec(batch=batch),
        max_seq_len=64, prefill_chunk_tokens=64,
    )


def _trace(n, *, seed=3, max_new=4):
    out = []
    for t in generate_trace(reduced_config(ARCH), n, arrival="poisson",
                            lengths="short_chat", rate_rps=50.0, seed=seed,
                            max_total_len=48):
        out.append(dataclasses.replace(t, max_new_tokens=max_new))
    return out


def _fleet(spec, params, **kw):
    return Fleet.from_spec(spec, emodel=EnergyModel(H200_SXM),
                           params_for=params, **kw)


class TestSpecs:
    def test_json_roundtrip_exact(self):
        spec = FleetSpec(
            replicas=(
                _rspec("a", ARCH, mode="slo", slo_tbt_s=0.5, slo_ttft_s=5.0,
                       context_scale=64.0),
                ReplicaSpec(
                    name="b", arch=ALT,
                    clock=ClockSpec(mode="cap", cap_w=450.0, fused=True),
                    decode=PoolSpec(batch=4, paged=True, kv_block_size=8,
                                    kv_blocks=48),
                    max_seq_len=64, prefill_chunk_tokens=32, rng_seed=7,
                ),
            ),
            router="energy",
            router_args={"headroom": 0.75},
        )
        assert FleetSpec.from_json(spec.to_json()) == spec
        # and the blob itself is stable (sorted keys)
        assert spec.to_json() == FleetSpec.from_json(spec.to_json()).to_json()

    def test_validation_fails_loudly(self):
        with pytest.raises(ValueError, match="mode"):
            ClockSpec(mode="turbo")
        with pytest.raises(ValueError, match="batch"):
            PoolSpec(batch=0)
        with pytest.raises(KeyError, match="unknown arch"):
            _rspec("x", arch="gpt-17t")
        with pytest.raises(ValueError, match="multiple"):
            ReplicaSpec(name="x", arch=ARCH, max_seq_len=60,
                        decode=PoolSpec(batch=2, paged=True, kv_block_size=16))
        with pytest.raises(ValueError, match="unique"):
            FleetSpec(replicas=(_rspec("dup"), _rspec("dup")))
        with pytest.raises(ValueError, match="unknown router"):
            FleetSpec(replicas=(_rspec("a"),), router="roulette")
        with pytest.raises(ValueError, match="at least one replica"):
            FleetSpec(replicas=())
        with pytest.raises(ValueError, match="unknown router"):
            make_router("roulette")

    def test_replica_lookup(self):
        spec = FleetSpec(replicas=(_rspec("a"), _rspec("b")))
        assert spec.replica("b").name == "b"
        with pytest.raises(KeyError):
            spec.replica("c")


class TestSingleReplicaEquivalence:
    def test_fleet_of_one_replays_byte_identical_to_cluster(self, setup):
        """The facade contract: a 1-replica Fleet and the Cluster facade
        must produce identical tokens, joules, and latency summaries."""
        trace = _trace(6)
        rspec = _rspec("solo")

        cluster = Cluster.from_spec(rspec, emodel=EnergyModel(H200_SXM),
                                    params=setup[ARCH], clock=VirtualClock())
        cdone = sorted(cluster.run_trace(trace), key=lambda r: r.uid)

        fleet = _fleet(FleetSpec(replicas=(rspec,)), setup)
        fdone = sorted(fleet.run_trace(trace), key=lambda r: r.uid)

        assert [r.output for r in cdone] == [r.output for r in fdone]
        blob = lambda done, decode_j, prefill_j, measured: json.dumps({
            "outputs": [r.output for r in done],
            "decode_j": decode_j, "prefill_j": prefill_j,
            "measured": measured,
            "lat": dataclasses.asdict(summarize_latency(done)),
        }, sort_keys=True)
        assert blob(cdone, cluster.decode_stats.decode_j,
                    cluster.prefill_stats.prefill_j,
                    cluster.measured_energy_j()) == \
            blob(fdone, fleet.stats.decode_j, fleet.stats.prefill_j,
                 fleet.measured_energy_j()["solo"])

    def test_engine_builds_from_spec(self, setup):
        eng = ServingEngine.from_spec(_rspec("eng"), emodel=EnergyModel(H200_SXM),
                                      params=setup[ARCH])
        assert eng.max_batch == 2 and eng.max_seq_len == 64
        req = eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=3)
        eng.run_to_completion()
        assert req.done and len(req.output) == 3
        assert eng.stats.prefill_j > 0      # spec-built controller attached


class TestRouters:
    def test_jsq_balances_submissions(self, setup):
        spec = FleetSpec(replicas=(_rspec("a"), _rspec("b")), router="jsq")
        fleet = _fleet(spec, setup)
        names = [fleet.submit(np.arange(1, 9, dtype=np.int32), 4).replica
                 for _ in range(4)]
        assert sorted(names) == ["a", "a", "b", "b"]
        assert names[0] != names[1]          # strict alternation from idle

    def test_routing_is_deterministic_across_replays(self, setup):
        spec = FleetSpec(
            replicas=(_rspec("g", ARCH), _rspec("m", ALT)), router="energy")
        trace = _trace(8, seed=11)

        def fingerprint():
            fleet = _fleet(spec, setup)
            done = fleet.run_trace(trace)
            done.sort(key=lambda r: (r.ledger.arrival_s, r.replica, r.uid))
            return json.dumps({
                "placement": [r.replica for r in done],
                "outputs": [r.output for r in done],
                "total_j": fleet.total_energy_j(),
                "lat": dataclasses.asdict(summarize_latency(done)),
            }, sort_keys=True)

        assert fingerprint() == fingerprint()

    def test_affinity_routes_by_modelled_request_energy(self, setup):
        spec = FleetSpec(
            replicas=(_rspec("g", ARCH), _rspec("m", ALT)), router="affinity")
        fleet = _fleet(spec, setup)
        prompt = np.arange(1, 33, dtype=np.int32)
        for bucket in ("short", "long"):
            cheapest = min(
                fleet.replicas,
                key=lambda r: r.controller.request_energy_mj(
                    len(prompt), 4, bucket))
            routed = fleet.route(prompt_len=len(prompt), max_new_tokens=4,
                                 bucket=bucket)
            assert routed is cheapest, bucket
        # untagged requests fall back to load balancing, not arch preference
        a = fleet.submit(prompt, 4, bucket="mixed")
        b = fleet.submit(prompt, 4, bucket="mixed")
        assert {a.replica, b.replica} == {"g", "m"}

    def test_energy_router_prices_both_phases(self, setup):
        """The marginal-joules signal must include prefill: it equals the
        controller's prompt x prefill/token + budget x decode/token."""
        spec = FleetSpec(replicas=(_rspec("g", ARCH),), router="energy")
        fleet = _fleet(spec, setup)
        r = fleet.replicas[0]
        router = fleet.router
        got = router._marginal_mj(r, 16, 8)
        ctl = r.controller
        dec = ctl.operating_point("decode", 1, 16 + 4.0)
        pre = ctl.operating_point("prefill", 1, 16 + 4.0)
        expect = 16 * pre.profile.energy_per_token_mj \
            + 8 * dec.profile.energy_per_token_mj
        assert got == pytest.approx(expect)


class TestRouterHeadroomEdges:
    """The headroom gate's boundary behaviour: a replica EXACTLY at
    ``headroom x decode slots`` is closed (strict <), saturation degrades
    to JSQ, and routing survives every replica draining at once."""

    def _loaded(self, replica, n):
        for _ in range(n):
            replica.submit(np.arange(1, 9, dtype=np.int32), 2)

    def test_energy_gate_closes_exactly_at_threshold(self, setup):
        spec = FleetSpec(replicas=(_rspec("g"), _rspec("m", ALT)),
                         router="energy")
        fleet = _fleet(spec, setup)     # batch=2, headroom=1.0 -> gate at 2
        g, m = fleet.replicas
        self._loaded(g, 2)              # queue_depth == 2: AT the gate
        assert g.queue_depth() == 1.0 * g.decode_pool.max_batch
        # g is closed even if it prices cheaper; the open replica wins
        assert fleet.route(prompt_len=8, max_new_tokens=2) is m

    def test_energy_degrades_to_jsq_when_every_gate_closed(self, setup):
        spec = FleetSpec(replicas=(_rspec("g"), _rspec("m", ALT)),
                         router="energy")
        fleet = _fleet(spec, setup)
        g, m = fleet.replicas
        self._loaded(g, 3)              # past the gate
        self._loaded(m, 2)              # at the gate
        # both closed: JSQ fallback -> least loaded, not cheapest joules
        assert fleet.route(prompt_len=8, max_new_tokens=2) is m

    def test_affinity_walks_ranking_past_gated_best(self, setup):
        spec = FleetSpec(replicas=(_rspec("g"), _rspec("m", ALT)),
                         router="affinity")
        fleet = _fleet(spec, setup)
        best = fleet.router.ranking(fleet.replicas, prompt_len=8,
                                    max_new_tokens=2, bucket="long")[0]
        other = next(r for r in fleet.replicas if r is not best)
        self._loaded(best, 2)           # best-ranked replica at the gate
        assert fleet.route(prompt_len=8, max_new_tokens=2,
                           bucket="long") is other

    def test_route_survives_every_replica_draining(self, setup):
        spec = FleetSpec(replicas=(_rspec("a"), _rspec("b")))
        fleet = _fleet(spec, setup)
        self._loaded(fleet.by_name["a"], 2)   # busy: drain keeps it powered
        self._loaded(fleet.by_name["b"], 1)
        fleet.drain("a")
        fleet.drain("b")
        assert not any(r.routable() for r in fleet.replicas)
        # powered fallback still serves, and still load-balances
        assert fleet.route(prompt_len=8, max_new_tokens=2).name == "b"

    def test_route_raises_with_everything_parked(self, setup):
        fleet = _fleet(FleetSpec(replicas=(_rspec("a"), _rspec("b"))), setup)
        fleet.drain("a")                # idle -> parks immediately
        fleet.drain("b")
        with pytest.raises(RuntimeError, match="no powered replica"):
            fleet.route(prompt_len=8, max_new_tokens=2)


class TestDrainPowerGating:
    def test_drained_replica_accrues_zero_joules(self, setup):
        spec = FleetSpec(replicas=(_rspec("live"), _rspec("parked")))
        trace = _trace(5)

        fleet = _fleet(spec, setup)
        fleet.drain("parked")
        done = fleet.run_trace(trace)
        assert len(done) == 5
        assert all(r.replica == "live" for r in done)
        parked = fleet.by_name["parked"]
        assert not parked.powered            # drained dry -> powered down
        assert fleet.measured_energy_j()["parked"] == \
            {"prefill": 0.0, "decode": 0.0}  # zero, NOT the idle floor
        assert sum(fleet.measured_energy_j()["live"].values()) > 0

        # control: the same replay without the drain burns idle-floor watts
        # on the second replica even for the work it never serves
        fleet2 = _fleet(spec, setup)
        fleet2.run_trace(trace)
        assert sum(fleet2.measured_energy_j()["parked"].values()) > 0

    def test_power_down_refuses_busy(self, setup):
        fleet = _fleet(FleetSpec(replicas=(_rspec("a"),)), setup)
        fleet.submit(np.arange(1, 9, dtype=np.int32), 4)
        with pytest.raises(RuntimeError, match="drain it first"):
            fleet.replicas[0].power_down()

    def test_power_up_restores_routing_and_idle_floor(self, setup):
        fleet = _fleet(FleetSpec(replicas=(_rspec("a"), _rspec("b"))), setup)
        fleet.drain("b")
        b = fleet.by_name["b"]
        assert not b.routable() and not b.powered
        assert b.decode_pool.idle_power_w == 0.0
        fleet.power_up("b")
        assert b.routable()
        assert b.decode_pool.idle_power_w == pytest.approx(H200_SXM.p_idle)

    def test_all_drained_still_serves_via_powered_fallback(self, setup):
        fleet = _fleet(FleetSpec(replicas=(_rspec("a"),)), setup)
        fleet.submit(np.arange(1, 9, dtype=np.int32), 2)   # in-flight work
        fleet.drain("a")                                   # draining, not parked
        r = fleet.route(prompt_len=8, max_new_tokens=2)
        assert r.name == "a"                               # nowhere else to go


class TestQueueDelaySummary:
    def test_summary_carries_queue_and_e2e_percentiles(self):
        class R:
            def __init__(self, q, e):
                self.ledger = LatencyLedger()
                self.ledger.mark_arrival(0.0)
                self.ledger.mark_admitted(q)
                self.ledger.mark_first_token(q + 0.1)
                self.ledger.mark_token(q + 0.2)
                self.ledger.mark_finish(e)
                self.output = [1, 2]

        lat = summarize_latency([R(1.0, 2.0), R(3.0, 4.0)])
        assert lat.p50_queue_s == pytest.approx(2.0)
        assert lat.mean_queue_s == pytest.approx(2.0)
        assert lat.p99_queue_s == pytest.approx(3.0, rel=0.01)
        assert lat.p95_e2e_s == pytest.approx(4.0, rel=0.05)

    def test_fleet_replay_reports_queue_delay(self, setup):
        fleet = _fleet(FleetSpec(replicas=(_rspec("a"),)), setup)
        done = fleet.run_trace(_trace(5))
        lat = summarize_latency(done)
        assert lat.p99_queue_s >= 0.0
        assert lat.p95_e2e_s >= lat.p50_e2e_s > 0.0


class TestOverlappedDecode:
    """A wall-clock replica's dense decode pool dispatches step N+1 before
    it reads step N's tokens (``PoolSpec.overlap``): the same tokens as the
    step-synchronous loop, with the host's work hidden behind the device's."""

    @staticmethod
    def _serve(params, arch, overlap, eos=None, batch=3):
        import time

        spec = FleetSpec(replicas=(ReplicaSpec(
            name="r0", arch=arch, clock=ClockSpec(mode="lock"),
            decode=PoolSpec(batch=batch, overlap=overlap), max_seq_len=64,
            prefill_chunk_tokens=64),))
        fleet = _fleet(spec, {arch: params}, clock=time.perf_counter)
        rng = np.random.default_rng(5)
        reqs = [fleet.submit(rng.integers(1, 200, size=int(rng.integers(5, 30))),
                             int(rng.integers(2, 12)), eos_token_id=eos)
                for _ in range(7)]
        while fleet.busy():
            fleet.step()
        return reqs, fleet.replicas[0].decode_pool

    @pytest.mark.parametrize("arch", [ARCH, ALT, "granite-4.0-h-micro"])
    @pytest.mark.parametrize("stop", ["max_new", "eos"])
    def test_overlap_serves_the_synchronous_tokens(self, setup, arch, stop):
        params = setup.get(arch) or init_params(reduced_config(arch),
                                                jax.random.PRNGKey(0))
        eos = None
        if stop == "eos":
            # a token the synchronous loop serves mid-stream: some requests
            # stop on it, and the overlapped loop has already dispatched the
            # step after, whose token for them it must drop
            ref, _ = self._serve(params, arch, False)
            eos = max(ref, key=lambda r: len(r.output)).output[2]
        sync, sync_pool = self._serve(params, arch, False, eos)
        ovl, ovl_pool = self._serve(params, arch, True, eos)
        assert not sync_pool.overlap and ovl_pool.overlap
        assert all(r.done for r in ovl)
        assert [r.output for r in ovl] == [r.output for r in sync]
        if stop == "eos":
            assert any(len(r.output) < r.max_new_tokens for r in sync)
        assert ovl_pool.stats.decode_tokens == sync_pool.stats.decode_tokens
        assert ovl_pool.occupancy() == 0

    def test_the_next_step_is_dispatched_before_the_tokens_are_read(self, setup):
        from repro.serving.pool import Pool

        events = []
        orig_finish = Pool._decode_finish

        def finish(pool, pre, *a):
            events.append("read")
            return orig_finish(pool, pre, *a)

        Pool._decode_finish = finish
        try:
            import time

            spec = FleetSpec(replicas=(_rspec("a", batch=2),))
            fleet = _fleet(spec, setup, clock=time.perf_counter)
            pool = fleet.replicas[0].decode_pool
            step = pool._jit_decode

            def dispatch(*a):
                events.append("dispatch")
                return step(*a)

            pool._jit_decode = dispatch
            for n in (9, 7):
                fleet.submit(np.arange(1, n, dtype=np.int32), 6)
            while fleet.busy():
                fleet.step()
        finally:
            Pool._decode_finish = orig_finish
        # each step's tokens are read only once the next step is on its way,
        # but for the last, which no step follows
        assert events[:3] == ["dispatch", "dispatch", "read"]
        assert events.count("dispatch") == events.count("read") == 5
        assert events[-2:] == ["read", "read"]

    def test_overlap_is_for_dense_wall_clock_pools(self, setup):
        import time

        from repro.serving.pool import Pool

        cfg = reduced_config(ARCH)
        kw = dict(max_batch=2, max_seq_len=64, overlap=True)
        assert Pool(cfg, setup[ARCH], clock=time.perf_counter, **kw).overlap
        assert not Pool(cfg, setup[ARCH], clock=VirtualClock(), **kw).overlap
        assert not Pool(cfg, setup[ARCH], clock=time.perf_counter, paged=True,
                        **kw).overlap
        assert PoolSpec().overlap
        spec = FleetSpec(replicas=(_rspec("a"),))
        assert FleetSpec.from_json(spec.to_json()) == spec
        # a virtual fleet built from the same spec keeps the synchronous loop
        assert not _fleet(spec, setup).replicas[0].decode_pool.overlap
