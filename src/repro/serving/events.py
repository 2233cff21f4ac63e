"""Discrete-event fleet engine: the per-fleet event heap over per-pool clocks.

The barrier driver (``Fleet.step``) advances every busy replica one tick
per round and syncs all clocks to the slowest — fidelity and throughput are
both capped by the round. This module replaces the round with a single
min-heap of events keyed on virtual time: trace arrivals, admission ticks,
decode steps, warm-up completions and autoscaler evaluations each fire when
their OWN dependencies are ready. Consequences:

* **Prefill overlaps decode.** Each replica's prefill pool runs on its own
  ``VirtualClock``; an admission prefill advances only that timeline, and
  the filled cache row is handed to the decode pool as a *pending
  placement* that joins the first decode step whose start time has reached
  the prefill's completion. A long prompt no longer pushes concurrent
  decode steps later, so prefill-burst TTFT matches a disaggregated
  deployment instead of a colocated one.
* **No global rounds.** Replicas interact only through arrivals (routing)
  and the autoscaler; a fast replica takes as many steps as fit in the
  time a slow one needs for one.
* **Fused homogeneous decode.** Decode events that pop at the same virtual
  time with the same model signature batch through ONE jitted call (each
  pool still splits its own RNG key and keeps its own accounting, so token
  streams are independent of grouping); at K aligned replicas this saves
  K-1 jit dispatches per step.
* **Batched replica axis** (``batch_replicas``, default on). The fused
  group's K independent replica steps run as ONE ``jax.vmap``-batched
  program over replica-stacked buffers instead of K traced sub-calls: the
  stacked KV/state caches persist between steps in a ``CacheBank``
  (``repro.serving.pool``) whose rows the member pools hold as views, so a
  stable group pays no stack/unstack work — XLA compiles one sub-graph
  instead of K and the donated stack updates in place. An opt-in
  ``batch_layout="shard_map"`` shards the replica axis over the host's
  devices (multi-device hosts run replica shards concurrently; bitwise
  identical to vmap since replicas never communicate).
  ``batch_replicas=False`` restores the PR-7 tuple-of-K program — the
  serial-fused byte-identity baseline the tests compare against.
* **Fused admission prefill.** Admission (ADMIT) events that pop at the
  same instant batch the same way: every admission decided across the
  drained events defers its ``_jit_prefill`` dispatch, the engine groups
  the deferred prefills by (config, params, prompt bucket) and runs each
  group as ONE jitted program of K independent batch-1 prefills, then
  replays the per-request accounting (clock advance, gauge bracketing,
  ledger stamps, RNG order, modelled joules) request-by-request in the
  exact order the serial path would have — byte-identical outputs, 1/K the
  dispatches. ``fuse_prefill=False`` restores the serial dispatch path
  (and is the byte-identity baseline the tests compare against).
* **Fusion quantum.** Exact-time fusion keys on ``t + _EPS`` ties, so a
  heterogeneous fleet whose clocks drift by one step defuses permanently.
  ``fusion_quantum_s=q`` widens the window: consecutive decode events at
  the TOP of the heap inside ``[t, t+q)`` drain into one dispatch batch.
  Timestamp semantics are unchanged — each pool still advances its own
  clock by its own modelled step time, only the dispatch is shared — and
  the window never crosses a non-decode event (an arrival or admission
  inside the window still orders before the later decode steps), so token
  streams are invariant under any quantum (property-tested). The default
  ``q=0`` is byte-identical to the exact-tie engine.

Event ordering at equal times is fixed by kind priority (warm-up
completions < arrivals < admissions < decode steps < autoscaler timers)
then by insertion sequence — the replay is a pure function of the trace.

Scale plumbing (the 10^6-requests / 100-replica path):

* Arrivals enter the heap LAZILY — one trace arrival is in flight at a
  time, so the heap stays O(replicas), not O(trace).
* Fused-dispatch group sizes bucket to powers of two (padded with inert
  repeats of the group's first member, results discarded), so the jit
  trace count on a drifting fleet stays O(log fleet) instead of one trace
  per distinct group size; the trace cache is a capped LRU, and the
  underlying jit programs are shared process-wide (like the per-pool
  ``_JIT_CACHE``), so fresh engines over the same fleet shape replay
  without recompiling.
* ``on_finish`` streams finished requests to a callback instead of
  accumulating them — with ``repro.serving.pool.release_request`` the
  replay runs memory-flat.
* ``EngineStats`` counts events, dispatches, fusion coverage and heap
  depth; ``Fleet.last_engine_stats`` hands it to benchmarks.

Semantics notes (parity with the barrier driver where timelines coincide):

* On a fleet whose pools share ONE clock (the single-replica ``Cluster``
  facade) prefill advances the decode timeline too, placements are always
  ready by the next decode pop, and the engine reproduces the barrier's
  step composition — token streams AND modelled joules are identical.
* Admission credit (``Scheduler``) accrues once per decode step — the
  barrier's chunked-prefill cadence. Arrival-time admission ticks only
  SPEND credit (``accrue=False``); an idle replica whose queue head needs
  more credit than one chunk spins zero-duration admission events, exactly
  like the barrier's zero-duration rounds.
* With an autoscaler, a timer event fires every ``tick_interval_s`` so
  hold windows and forecasts evaluate mid-gap (the barrier driver gets the
  same via ``Fleet._cross_idle_gap``).
"""
from __future__ import annotations

import dataclasses
import heapq
from collections import OrderedDict
from typing import (
    Any, Callable, Deque, Dict, List, Optional, Set, Tuple, TYPE_CHECKING,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import shard_map_replicas, vmap_replicas
from repro.serving.pool import (
    BankRow, CacheBank, Pool, Request, row_on_device, observe_latencies,
    requeue_front,
)
from repro.serving.spans import span

BATCH_LAYOUTS = ("vmap", "shard_map")

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.serving.fleet import Fleet, Replica

__all__ = ["EngineStats", "EventDrivenFleet"]

# pop order at equal virtual time: a warm-up that ends exactly when a
# request arrives must admit it; an admission decided at t feeds the decode
# step at t; the autoscaler sees the post-step world
PRIO_WARM, PRIO_ARRIVAL, PRIO_ADMIT, PRIO_DECODE, PRIO_SCALE = range(5)

_EPS = 1e-12

# Process-wide fused jit programs, keyed on what the TRACE depends on (the
# underlying per-pool impl — itself shared via ``pool._JIT_CACHE`` — plus
# any static trace constants). The per-engine ``_fused_cache`` keeps its
# capped-LRU (kind, sig, pow2) bookkeeping, but cache misses resolve here
# first, so a benchmark that replays the same fleet shape through several
# fresh engines compiles each fused program once per process, not once per
# engine. Capped LRU like ``pool._JIT_CACHE`` (the closures retain params
# and compiled executables); ``pool.clear_program_caches()`` empties it.
_PROGRAM_CACHE_CAP = 128
_PROGRAM_CACHE: "OrderedDict[Tuple[Any, ...], Any]" = OrderedDict()


def _program(key: Tuple[Any, ...], make: Callable[[], Any]):
    fn = _PROGRAM_CACHE.get(key)
    if fn is None:
        fn = _PROGRAM_CACHE[key] = make()
        while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_CAP:
            _PROGRAM_CACHE.popitem(last=False)
    else:
        _PROGRAM_CACHE.move_to_end(key)
    return fn


def _host_mesh(layout: str, p2: int) -> Optional[Tuple[Any, ...]]:
    """The devices an unbound fused group of padded size ``p2`` shards its
    replica axis over: all of the host's, when ``layout`` asks for
    ``shard_map`` and ``p2`` divides evenly over more than one device;
    otherwise None, and the group runs as plain ``vmap`` on one device
    (``EngineStats.vmap_fallbacks`` counts those under ``shard_map``)."""
    n_dev = len(jax.devices())
    if layout == "shard_map" and n_dev > 1 and p2 % n_dev == 0:
        return tuple(jax.devices())
    return None


def _batched_core(impl, devices: Optional[Tuple[Any, ...]]):
    """The replica-batched decode body: params broadcast, everything else
    stacked along the leading replica axis — ``shard_map`` over
    ``devices`` (row blocks in device order), or plain ``vmap`` when None.
    Module-level so the process-wide program cache never retains an engine
    through the traced closure."""
    if devices is None:
        return vmap_replicas(impl, 7)
    return shard_map_replicas(impl, 7, devices=list(devices))


def _stack_decode_args(pres: List[dict], pad: int):
    """A fused chunk's dense decode arguments (tokens, lengths, active,
    RNG keys, temperatures) stacked along a leading replica axis, padded
    with ``pad`` inert repeats of member 0. Keys stay a tuple: they stack
    inside the program."""
    rows = [pre["args"] for pre in pres]
    rows += [rows[0]] * pad
    stack = lambda i: np.stack([a[i] for a in rows])
    return stack(1), stack(3), stack(4), tuple(a[5] for a in rows), stack(6)


def _bank_step_program(impl, devices: Optional[Tuple[Any, ...]], p2: int):
    """The coherent-bank batched decode program: the stacked cache is
    donated and the output tree replaces it, so a stable group pays no
    stack/unstack work. ``devices`` as in ``_batched_core``."""
    def make():
        def fused(params, cache, toks, lengths, active, keys, temps):
            core = _batched_core(impl, devices)
            return core(params, toks, cache, lengths, active,
                        jnp.stack(keys), temps)

        return jax.jit(fused, donate_argnums=(1,))

    return _program(("decode_batched", impl, devices, p2), make)


@dataclasses.dataclass(slots=True)
class EngineStats:
    """Counter block for one event-engine replay — the observability the
    scale work needs to see where the next bottleneck moves. Written into
    every serving benchmark's JSON artifact via ``as_dict``."""

    events: int = 0                    # heap pops, every kind
    events_by_kind: Dict[str, int] = dataclasses.field(default_factory=dict)
    peak_heap: int = 0                 # max heap length observed
    decode_steps: int = 0              # per-pool decode steps executed
    placements: int = 0                # cache rows placed into decode slots
    prefills: int = 0                  # admission prefills run
    fused_prefill_calls: int = 0       # batched prefill jit dispatches
    serial_prefill_calls: int = 0      # one-request prefill jit dispatches
    fused_prefill_reqs: int = 0        # prefills served by fused dispatches
    fused_decode_calls: int = 0        # multi-pool decode jit dispatches
    serial_decode_calls: int = 0       # one-pool decode jit dispatches
    batched_decode_calls: int = 0      # fused decode dispatches that ran as
                                       # ONE vmap/shard_map-batched program
                                       # (subset of fused_decode_calls)
    batched_prefill_calls: int = 0     # ditto for fused admission prefill
    shard_map_calls: int = 0           # batched decode dispatches laid out
                                       # over a device mesh with shard_map
    vmap_fallbacks: int = 0            # batched decode dispatches that asked
                                       # for shard_map but ran as vmap on one
                                       # device (group size not a multiple
                                       # of the host's device count)
    bank_gathers: int = 0              # churned groups re-stacked by an
                                       # in-program index gather off ONE
                                       # still-resident bank (cheap)
    bank_rebuilds: int = 0             # batched groups re-stacked the hard
                                       # way: rows materialised from mixed
                                       # banks / dense trees, stacked in-jit
    fused_traces: int = 0              # fused jit programs built (LRU inserts)
    pad_waste: int = 0                 # inert pad slots across fused calls
    pool_jit_dispatches: int = 0       # serial dispatches made by the pools
                                       # (prefill + scatter + serial decode)
    # prefix-sharing counters (pool lifetime, summed over decode pools at
    # run() end — all-zero on fleets with sharing off; the full breakdown
    # rides in ``prefix_stats``)
    prefix_hits: int = 0
    prefix_shared_blocks: int = 0
    prefix_cow_splits: int = 0
    saved_prefill_j: float = 0.0
    prefix_stats: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def jit_dispatches(self) -> int:
        """Total XLA dispatches this replay paid (fused + serial)."""
        return (self.pool_jit_dispatches + self.fused_decode_calls
                + self.fused_prefill_calls)

    @property
    def fused_prefill_coverage(self) -> float:
        """Fraction of admission prefills served by a fused dispatch."""
        return self.fused_prefill_reqs / self.prefills if self.prefills else 0.0

    @property
    def fused_decode_coverage(self) -> float:
        """Fraction of pool decode steps served by a fused dispatch."""
        if not self.decode_steps:
            return 0.0
        return (self.decode_steps - self.serial_decode_calls) / self.decode_steps

    def as_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["jit_dispatches"] = self.jit_dispatches
        d["fused_prefill_coverage"] = self.fused_prefill_coverage
        d["fused_decode_coverage"] = self.fused_decode_coverage
        return d


class EventDrivenFleet:
    """One trace replay, event-driven. Build per ``run_trace`` call.

    ``fusion_quantum_s`` widens decode-dispatch fusion from exact virtual-
    time ties to the half-open window ``[t, t+q)`` (see module docstring);
    0 is byte-identical to the exact-tie engine. ``fuse_prefill`` toggles
    the batched admission-prefill path (True by default; False is the
    serial PR-6 dispatch behaviour and the byte-identity baseline).
    ``max_fused_group`` caps how many per-pool bodies one fused program
    traces (rounded up to a power of two; larger batches chunk).
    ``on_finish`` streams each finished request to the callback INSTEAD of
    accumulating it in the returned list — the memory-flat path for
    million-request replays (pair with ``pool.release_request``)."""

    def __init__(self, fleet: "Fleet", *, fast_path_min: int = 4,
                 fusion_quantum_s: float = 0.0,
                 fuse_prefill: bool = True,
                 max_fused_group: int = 64,
                 fused_cache_cap: int = 64,
                 batch_replicas: bool = True,
                 batch_layout: str = "vmap",
                 on_finish: Optional[Callable[[Request], None]] = None):
        if not fleet.virtual:
            raise ValueError("the event engine needs VirtualClock replicas")
        if fusion_quantum_s < 0:
            raise ValueError("fusion_quantum_s must be >= 0")
        if max_fused_group < 1:
            raise ValueError("max_fused_group must be >= 1")
        if batch_layout not in BATCH_LAYOUTS:
            raise ValueError(f"batch_layout {batch_layout!r} not in "
                             f"{BATCH_LAYOUTS}")
        self.fleet = fleet
        self.fast_path_min = max(2, int(fast_path_min))
        self.fusion_quantum_s = float(fusion_quantum_s)
        self.fuse_prefill = bool(fuse_prefill)
        # pow2 so chunk sizes bucket onto themselves
        self.max_fused_group = 1 << (int(max_fused_group) - 1).bit_length()
        self.fused_cache_cap = max(4, int(fused_cache_cap))
        # batch_replicas=True (the default) runs each fused group as ONE
        # vmap-batched program over replica-stacked buffers; False keeps the
        # PR-7 tuple-of-K program — the serial-fused byte-identity baseline
        # and the opt-out flag for shapes where per-replica tracing wins
        self.batch_replicas = bool(batch_replicas)
        self.batch_layout = batch_layout
        # device-bound pools (``Fleet.from_spec(devices=...)``) under
        # shard_map fuse ACROSS devices: one program over a mesh of their
        # devices, each replica's bank row on its own device
        self._mesh = self.batch_replicas and batch_layout == "shard_map"
        # replicated weights assembled from the members' per-device copies,
        # keyed (params_token, devices)
        self._mesh_params: Dict[Tuple[Any, ...], Any] = {}
        self.on_finish = on_finish
        self.stats = EngineStats()
        self._heap: List[Tuple[float, int, int, str, Any]] = []
        self._seq = 0
        self._real = 0                     # outstanding non-timer events
        # per replica: prefilled-but-not-placed rows as MUTABLE entries
        # [ready_s, req, cache1, first] in admission order (the fused
        # admission path appends placeholders during the scheduler tick and
        # fills them after the batched dispatch)
        self._pending: Dict[str, List[List[Any]]] = {
            r.name: [] for r in fleet.replicas}
        # per replica: virtual time of the scheduled decode event, or None
        self._decode_at: Dict[str, Optional[float]] = {
            r.name: None for r in fleet.replicas}
        # per replica: requests placed since its last decode step (the
        # TTFT population observe_latencies feeds the slo loop)
        self._obs: Dict[str, List[Request]] = {r.name: [] for r in fleet.replicas}
        # per replica: outstanding admission events. While one is in flight
        # an arrival just enqueues — the scheduled tick at >= t will see it,
        # exactly the barrier's release-then-tick round top
        self._admit_sched: Dict[str, int] = {r.name: 0 for r in fleet.replicas}
        self._warm_sched: Set[Tuple[str, float]] = set()
        self._scale_pending: Set[float] = set()
        # capped LRU of fused jit programs, keyed (kind, sig, pow2 size)
        self._fused_cache: "OrderedDict[Tuple[Any, ...], Any]" = OrderedDict()
        self._steps = 0
        # lazy arrival feed: one trace arrival in the heap at a time
        self._trace: List[Any] = []
        self._next_arrival = 0
        self._tick_interval = 0.0
        if fleet.autoscaler is not None:
            self._tick_interval = float(getattr(
                getattr(fleet.autoscaler, "spec", None),
                "tick_interval_s", 0.0) or 0.0)

    # --------------------------------------------------------------- back-compat
    @property
    def fused_calls(self) -> int:
        """Fused decode dispatches (the PR-6 counter name)."""
        return self.stats.fused_decode_calls

    # ----------------------------------------------------------- heap basics
    def _push(self, t: float, prio: int, kind: str, payload: Any):
        heapq.heappush(self._heap, (t, prio, self._seq, kind, payload))
        self._seq += 1
        if prio != PRIO_SCALE:
            self._real += 1
        if len(self._heap) > self.stats.peak_heap:
            self.stats.peak_heap = len(self._heap)

    def _pop(self):
        ev = heapq.heappop(self._heap)
        if ev[1] != PRIO_SCALE:
            self._real -= 1
        st = self.stats
        st.events += 1
        kind = ev[3]
        st.events_by_kind[kind] = st.events_by_kind.get(kind, 0) + 1
        return ev

    def _push_admit(self, name: str, t: float, accrue: bool):
        self._admit_sched[name] += 1
        self._push(t, PRIO_ADMIT, "admit", (name, accrue))

    def _push_next_arrival(self):
        """Feed the next trace arrival into the heap. Arrivals are sorted,
        so holding exactly one keeps the heap O(replicas) deep at 10^6
        requests while popping in the same order an eager fill would (heap
        ties only compare the insertion sequence WITHIN one (t, priority)
        class, and only one trace arrival is ever in flight)."""
        i = self._next_arrival
        if i < len(self._trace):
            self._next_arrival = i + 1
            self._push(self._t_start + self._trace[i].arrival_s,
                       PRIO_ARRIVAL, "arrival", i)

    def _fused_fn(self, key: Tuple[Any, ...], build: Callable[[], Any]):
        """Capped-LRU lookup of a fused jit program."""
        cache = self._fused_cache
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = build()
            self.stats.fused_traces += 1
            while len(cache) > self.fused_cache_cap:
                cache.popitem(last=False)
        else:
            cache.move_to_end(key)
        return fn

    @staticmethod
    def _pow2(n: int) -> int:
        return 1 << (n - 1).bit_length()

    # ------------------------------------------------------------ clock utils
    @staticmethod
    def _catch_up(pool: Pool, t: float):
        """Advance an idle/lagging pool timeline to the event time, sampling
        so the wait integrates at its gauge power (idle floor when empty)."""
        if pool.clock.now_s < t:
            pool.clock.advance_to(t)
            pool.sample_now()

    # ------------------------------------------------------------------- run
    def run(self, trace, *, max_steps: int = 1000000) -> List[Request]:
        fleet = self.fleet
        self._trace = sorted(trace, key=lambda t: t.arrival_s)
        self._t_start = t_start = fleet.now_s()
        base_dispatch = sum(p.jit_dispatches for r in fleet.replicas
                            for p in (r.prefill_pool, r.decode_pool))
        self._push_next_arrival()
        for r in fleet.replicas:
            if r.powered and r._warming_until_s is not None:
                self._schedule_warm(r)
            # work queued/live before run() (Cluster.submit + run_trace)
            if r.decode_pool.occupancy() > 0:
                self._ensure_decode(r)
            elif r.waiting:
                self._push_admit(r.name, r.max_clock_s(), True)
        if fleet.autoscaler is not None and self._tick_interval > 0:
            self._push(t_start + self._tick_interval, PRIO_SCALE, "scale", None)
        done: List[Request] = []
        quantum = self.fusion_quantum_s
        fleet.start_metering()
        try:
            while self._heap and self._steps < max_steps:
                t, prio, _, kind, payload = self._pop()
                if kind == "decode":
                    # batch decode events at the SAME instant — and, with a
                    # fusion quantum, every decode event at the top of the
                    # heap inside [t, t+q). A replica's decode event is
                    # always preceded by its own post-step ADMIT event at
                    # the same stamp, so the window also processes ADMIT
                    # events inside it for replicas NOT already drained
                    # (disjoint per-replica state: the reorder against an
                    # earlier replica's decode step is unobservable). Only
                    # dispatch grouping changes — each pool still steps at
                    # its own scheduled time on its own clock; arrivals,
                    # warm-ups, autoscaler events, or a repeat replica
                    # terminate the window, so routing and same-replica
                    # sequencing keep the exact-tie order
                    names = [payload]
                    seen = {payload}
                    win = t + quantum
                    while self._heap:
                        t0, p0 = self._heap[0][0], self._heap[0][1]
                        if (p0 == PRIO_DECODE
                                and (t0 <= t + _EPS or t0 < win)
                                and self._heap[0][4] not in seen):
                            names.append(self._pop()[4])
                            seen.add(names[-1])
                        elif (quantum > 0.0 and p0 == PRIO_ADMIT
                              and t0 < win
                              and self._heap[0][4][0] not in seen):
                            ev = self._pop()
                            name, accrue = ev[4]
                            self._admit_sched[name] -= 1
                            r = fleet.by_name[name]
                            self._admit(r, ev[0], accrue=accrue)
                            self._after_admit(r)
                        else:
                            break
                    finished = self._decode_batch(names, t)
                    if self.on_finish is not None:
                        for q in finished:
                            self.on_finish(q)
                    else:
                        done.extend(finished)
                elif kind == "arrival":
                    self._push_next_arrival()
                    self._handle_arrival(self._trace[payload], t)
                elif kind == "admit":
                    if not self.fuse_prefill:
                        name, accrue = payload
                        self._admit_sched[name] -= 1
                        r = fleet.by_name[name]
                        self._admit(r, t, accrue=accrue)
                        self._after_admit(r)
                    else:
                        # drain same-instant admission events for DISTINCT
                        # replicas: their scheduler ticks are independent,
                        # so the decided prefills can share one dispatch.
                        # A repeat of a replica ends the drain — its second
                        # tick depends on the first's placements
                        batch = [payload]
                        seen = {payload[0]}
                        while (self._heap
                               and self._heap[0][1] == PRIO_ADMIT
                               and self._heap[0][0] <= t + _EPS
                               and self._heap[0][4][0] not in seen):
                            ev = self._pop()
                            batch.append(ev[4])
                            seen.add(ev[4][0])
                        self._admit_batch(batch, t)
                elif kind == "warm":
                    self._handle_warm(fleet.by_name[payload], t)
                elif kind == "scale":       # the autoscaler's periodic timer
                    self._handle_scale(t)
                else:                       # "autoscale": one-shot round end
                    self._scale_pending.discard(t)
                    self._autoscale()
        finally:
            # pull every pool to the fleet's final time so lagging idle
            # floors integrate to the horizon the barrier would have reached
            t_end = fleet.now_s()
            for r in fleet.replicas:
                r.advance_all(t_end)
            fleet.stop_metering()
            st = self.stats
            st.decode_steps = self._steps
            st.pool_jit_dispatches = sum(
                p.jit_dispatches for r in fleet.replicas
                for p in (r.prefill_pool, r.decode_pool)) - base_dispatch
            ps = fleet.prefix_stats_total()
            st.prefix_hits = ps.hits
            st.prefix_shared_blocks = ps.shared_blocks
            st.prefix_cow_splits = ps.cow_splits
            st.saved_prefill_j = ps.saved_prefill_j
            st.prefix_stats = ps.as_dict()
            fleet.last_engine_stats = st
        return done

    # --------------------------------------------------------------- arrivals
    def _handle_arrival(self, tr, t: float):
        fleet = self.fleet
        if (fleet.autoscaler is not None and self._tick_interval <= 0
                and not fleet.busy()):
            # timer-less mode: the barrier autoscales once at the end of an
            # all-idle gap, after advancing every clock across it
            for r in fleet.replicas:
                r.advance_all(t)
            self._autoscale()
        req = fleet.submit(tr.prompt, tr.max_new_tokens,
                           temperature=tr.temperature,
                           eos_token_id=tr.eos_token_id, arrival_s=t,
                           bucket=tr.bucket)
        r = fleet.by_name[req.replica]
        if r._warming_until_s is not None and t < r._warming_until_s - _EPS:
            self._schedule_warm(r)          # admission fires when warm
        elif self._admit_sched[r.name] == 0:
            # spend-only tick: credit accrues per decode step (or on a
            # fresh, fully idle replica — the barrier's first round).
            # With an admission event already in flight the request just
            # enqueues: the scheduled tick sees it, the barrier's
            # release-arrivals-then-tick order at a round top
            fresh = (self._decode_at[r.name] is None
                     and not self._pending[r.name])
            self._admit(r, t, accrue=fresh)
            self._after_admit(r)

    # -------------------------------------------------------------- admission
    def _admit_tick(self, r: "Replica", t: float, *, accrue: bool,
                    collect: Optional[List[Tuple[Pool, Request, List[Any]]]] = None):
        """One scheduler tick at event time ``t`` on the replica's prefill
        timeline. Prefilled rows become pending placements; the decode
        timeline picks them up in ``_flush``.

        With ``collect`` given, the admission prefill DISPATCH is deferred:
        each admitted request appends a mutable placeholder to the pending
        list (the gate closure only reads entry count + prompt lengths, so
        capacity accounting is exact) and a job onto ``collect``; the
        caller runs the batched dispatch and then fills every placeholder
        through ``Pool.prefill_request(precomputed=...)`` in admission
        order — the per-pool clock/gauge/RNG/stamp sequence is untouched."""
        if not r.powered or (r._warming_until_s is not None
                             and t < r._warming_until_s - _EPS):
            return None
        pp, dp = r.prefill_pool, r.decode_pool
        self._catch_up(pp, t)
        if not r.waiting:
            r.scheduler.tick(r.waiting, pp, dp)     # credit reset, empty queue
            return None
        if r.controller is not None:
            r._step_no += 1
            r.controller.tick(r.pools(), r._step_no)
        pend = self._pending[r.name]
        st = self.stats

        def gate(req: Request) -> bool:
            # can_admit, minus capacity already promised to pending rows
            if len(dp.free_slots()) <= len(pend):
                return False
            if dp.paged:
                need = dp.allocator.blocks_for_tokens(len(req.prompt) + 1)
                extra = 0
                if dp._prefix is not None:
                    # shared entries the candidate would reuse need no fresh
                    # blocks; index pages NOT reused stay reclaimable. The
                    # pending rows' held stays the conservative full need
                    # (their hits are already acquired, so double-counting
                    # is impossible — just pessimistic)
                    entries, _ = dp._peek_fitted(req.prompt)
                    need = max(need - entries, 0)
                    extra = max(dp._prefix.reclaimable_blocks() - entries, 0)
                held = sum(dp.allocator.blocks_for_tokens(len(e[1].prompt) + 1)
                           for e in pend)
                return need + held <= dp.allocator.free_blocks + extra
            return True

        if collect is None:
            def admit(req: Request) -> None:
                hit = dp.prefix_acquire(req)
                first, cache1 = pp.prefill_request(req, shared=hit, donor=dp)
                pend.append([pp.clock.now_s, req, cache1, first])
                st.prefills += 1
                st.serial_prefill_calls += 1
        else:
            def admit(req: Request) -> None:
                # acquire NOW (tick order fixes capacity + stats order);
                # the dispatch itself is deferred to the fused phase. The
                # hit travels with the job — placement re-finds it via the
                # pool's own _pending_hits stash
                hit = dp.prefix_acquire(req)
                entry: List[Any] = [None, req, None, None]
                pend.append(entry)
                collect.append((pp, req, entry, hit, dp))

        admitted = r.scheduler.tick(r.waiting, pp, dp,
                                    admit=admit, gate=gate, accrue=accrue)
        return {"admitted": admitted, "gate": gate}

    def _admit_finish(self, r: "Replica", info: Optional[Dict[str, Any]]):
        """The post-tick half of an admission: log the tick's admissions
        (their ledgers are stamped by now even on the fused path) and spin
        a zero-duration admission event for a long queue head."""
        if info is None:
            return
        for req in info["admitted"]:
            r.admit_log.append((req.ledger.admitted_s, req.ledger.queue_s))
        pend = self._pending[r.name]
        if (r.waiting and not info["admitted"] and not pend
                and self._decode_at[r.name] is None
                and self._admit_sched[r.name] == 0
                and r.decode_pool.occupancy() == 0
                and info["gate"](r.waiting[0])
                and len(r.waiting[0].prompt) > r.scheduler._credit):
            # idle replica, long head: spin zero-duration admission events
            # until accrued credit covers the prompt — the barrier's
            # frozen-clock rounds, bounded at ceil(prompt/chunk) spins
            self._push_admit(r.name, r.prefill_pool.clock.now_s, True)

    def _admit(self, r: "Replica", t: float, *, accrue: bool):
        """Single-replica admission (arrival-path / warm-path / single
        ADMIT event). With ``fuse_prefill`` on, a tick that admits K
        requests still runs ONE grouped dispatch; with it off, every
        prefill dispatches inline inside the scheduler tick (the serial
        baseline)."""
        if not self.fuse_prefill:
            self._admit_finish(r, self._admit_tick(r, t, accrue=accrue))
            return
        jobs: List[Tuple[Pool, Request, List[Any], Any, Pool]] = []
        info = self._admit_tick(r, t, accrue=accrue, collect=jobs)
        if jobs:
            self._prefill_fused(jobs)
        self._admit_finish(r, info)

    def _admit_batch(self, batch: List[Tuple[str, bool]], t: float):
        """Process a drained batch of same-instant admission events for
        distinct replicas: collect every decided admission with its prefill
        dispatch deferred, run the grouped dispatches, then finish each
        replica in event order. Equivalent to processing the events
        serially because the ticks touch disjoint replica state, the
        deferred accounting replays in admission order, and every heap push
        (spin admits, decode events) happens in the finish phase in the
        same per-replica order the serial engine uses."""
        fleet = self.fleet
        jobs: List[Tuple[Pool, Request, List[Any], Any, Pool]] = []
        infos: List[Tuple["Replica", Optional[Dict[str, Any]]]] = []
        for name, accrue in batch:
            self._admit_sched[name] -= 1
            r = fleet.by_name[name]
            infos.append((r, self._admit_tick(r, t, accrue=accrue,
                                              collect=jobs)))
        if jobs:
            self._prefill_fused(jobs)
        for r, info in infos:
            self._admit_finish(r, info)
            self._after_admit(r)

    def _prefill_fused(self, jobs: List[Tuple[Pool, Request, List[Any], Any, Pool]]):
        """Run every deferred admission prefill in grouped jitted dispatches
        and fill the pending-placement placeholders. Grouping is by
        (config, params, max_seq_len, prompt bucket); group sizes chunk at
        ``max_fused_group`` and pad to powers of two with an inert repeat
        of the group's first prompt (results discarded), so the program
        cache stays O(log fleet) on drifting group sizes. The per-request
        accounting replays afterwards IN JOB ORDER — each pool sees its
        admissions in exactly the serial sequence.

        Prefix-hit jobs never join a fused group: a suffix prefill gathers
        from its donor's live paged cache, which the NEXT hit in the same
        batch may extend — so each one dispatches individually (counted
        serial) and only its accounting replays at its job-order slot."""
        st = self.stats
        groups: Dict[Tuple[Any, ...], List[Tuple[Pool, Any, Any, int, List[Any]]]] = {}
        order: List[Tuple[Any, ...]] = []
        for pp, req, entry, hit, dp in jobs:
            if hit is not None:
                continue
            toks, true_len, bucket = pp.prefill_tokens(req)
            # params_token (not id(params)): a stable monotonic identity
            # that a GC'd fleet can never hand to a different pool's weights;
            # the device keeps a group's rows (and weights) on one chip
            sig = (pp.cfg, pp.params_token, pp.max_seq_len, bucket, pp.device)
            g = groups.get(sig)
            if g is None:
                g = groups[sig] = []
                order.append(sig)
            g.append((pp, toks, true_len, bucket, entry))
        results: Dict[int, Tuple[Any, Any]] = {}
        for sig in order:
            items = groups[sig]
            for i in range(0, len(items), self.max_fused_group):
                self._prefill_fused_chunk(sig, items[i:i + self.max_fused_group],
                                          results)
        for pp, req, entry, hit, dp in jobs:
            if hit is not None:
                first, cache1 = pp.prefill_request(req, shared=hit, donor=dp)
                st.serial_prefill_calls += 1
            else:
                first, cache1 = pp.prefill_request(
                    req, precomputed=results[id(entry)])
                st.fused_prefill_reqs += 1
            entry[0] = pp.clock.now_s
            entry[2] = cache1
            entry[3] = first
            st.prefills += 1

    def _prefill_fused_chunk(self, sig, items, results: Dict[int, Any]):
        """One fused prefill dispatch: K (pow2-padded) independent batch-1
        bucketed prefills in one program. Identical per-request
        computations to the serial ``_jit_prefill`` calls — only the
        dispatch is shared (the same argument the fused decode path
        already proves byte-exactly).

        With ``batch_replicas`` (default) the program is ONE vmapped
        prefill over (P, 1, bucket)-stacked prompts, sliced back to the
        per-request tuple inside jit; without it, K traced sub-calls (the
        PR-7 tuple program)."""
        st = self.stats
        k = len(items)
        p = self._pow2(k)
        pp0, toks0, len0, bucket, _ = items[0]
        if self.batch_replicas:
            toks = np.stack([it[1] for it in items]
                            + [toks0] * (p - k))          # (P, 1, bucket)
            lens = np.stack([it[2] for it in items]
                            + [len0] * (p - k))           # (P, 1)

            def build(p=p):
                impl = pp0._prefill_impl

                def make():
                    def fused(params, toks, lens):
                        vf = vmap_replicas(
                            lambda pr, tk, ln: impl(pr, tk, ln, bucket), 3)
                        logits, cache1 = vf(params, toks, lens)
                        # per-request tuple OUT of jit so the precomputed
                        # handoff consumes rows exactly like the tuple path
                        return tuple(
                            (logits[i],
                             jax.tree.map(lambda x, i=i: x[i], cache1))
                            for i in range(p))

                    return jax.jit(fused)

                return _program(("prefill_batched", impl, bucket, p), make)

            fn = self._fused_fn(("prefill", sig, p), build)
            outs = fn(pp0.params, toks, lens)
            st.batched_prefill_calls += 1
        else:
            toks = [it[1] for it in items] + [toks0] * (p - k)
            lens = [it[2] for it in items] + [len0] * (p - k)

            def build():
                impl = pp0._prefill_impl

                def make():
                    def fused(params, toks, lens):
                        return tuple(impl(params, tk, ln, bucket)
                                     for tk, ln in zip(toks, lens))

                    return jax.jit(fused)

                return _program(("prefill", impl, bucket), make)

            fn = self._fused_fn(("prefill", sig, p), build)
            outs = fn(pp0.params, tuple(toks), tuple(lens))
        st.fused_prefill_calls += 1
        st.pad_waste += p - k
        for it, out in zip(items, outs):
            results[id(it[4])] = out

    def _flush(self, r: "Replica"):
        """Place pending prefilled rows whose handoff time the decode
        timeline has reached — every consecutively-ready row in ONE
        ``place_many`` scatter dispatch; an IDLE decode pool jumps forward
        to the earliest handoff instead (sampling its gauge across the
        wait)."""
        pend = self._pending[r.name]
        dp = r.decode_pool
        while pend:
            batch = []
            while pend:
                ready, req, cache1, first = pend[0]
                if ready is None or ready > dp.clock.now_s + _EPS:
                    break
                pend.pop(0)
                batch.append((req, cache1, first, len(req.prompt), ready))
            if batch:
                dp.place_many(batch)
                obs = self._obs[r.name]
                for item in batch:
                    obs.append(item[0])
                self.stats.placements += len(batch)
                continue                    # occupancy changed: re-evaluate
            ready = pend[0][0]
            if (ready is None or dp.occupancy() > 0
                    or self._decode_at[r.name] is not None):
                break                       # joins a later step
            self._catch_up(dp, ready)

    def _ensure_decode(self, r: "Replica"):
        """Schedule the replica's next decode event: now for live slots,
        the earliest handoff for a pool waiting on its first placement."""
        if self._decode_at[r.name] is not None:
            return
        if r.decode_pool.occupancy() > 0:
            t = r.decode_pool.clock.now_s
        elif self._pending[r.name]:
            # a handoff decided mid-step can be ready before the step's end;
            # the event still fires at the decode timeline's present
            t = max(self._pending[r.name][0][0], r.decode_pool.clock.now_s)
        else:
            return
        self._decode_at[r.name] = t
        self._push(t, PRIO_DECODE, "decode", r.name)

    def _after_admit(self, r: "Replica"):
        self._flush(r)
        self._ensure_decode(r)

    # ----------------------------------------------------------- decode steps
    def _decode_batch(self, names: List[str], t: float) -> List[Request]:
        fleet = self.fleet
        reps = [fleet.by_name[n] for n in names]
        for r in reps:
            self._decode_at[r.name] = None
            self._flush(r)
        live = [r for r in reps if r.decode_pool.occupancy() > 0]
        for r in live:
            if r.controller is not None:
                r._step_no += 1
                r.controller.tick(r.pools(), r._step_no)
        finished_by = self._run_decodes(live)
        done: List[Request] = []
        for r in live:
            finished = finished_by[r.name]
            if r.controller is not None:
                observe_latencies(r.controller, r.decode_pool,
                                  self._obs.pop(r.name, []), finished)
                self._obs[r.name] = []
            requeue_front(r.waiting, r.decode_pool.take_evicted())
            done.extend(finished)
            self._steps += 1
            # post-step admission as an ADMIT event at the step's end —
            # arrivals stamped inside the step pop first (earlier heap
            # times, lower prio at a tie), so the accrual tick sees them
            # enqueued: the barrier's release-then-tick round top
            self._push_admit(r.name, r.decode_pool.clock.now_s, True)
            self._ensure_decode(r)
        for r in reps:
            if r not in live:
                self._after_admit(r)        # pending handoff still ahead
        fleet._power_down_drained()
        if (fleet.autoscaler is not None and self._tick_interval <= 0
                and live):
            # timer-less mode evaluates once per "round", after the round's
            # admissions land — a one-shot event behind the admit events
            t_end = max(r.decode_pool.clock.now_s for r in live)
            if t_end not in self._scale_pending:
                self._scale_pending.add(t_end)
                self._push(t_end, PRIO_SCALE, "autoscale", None)
        return done

    def _run_decodes(self, live: List["Replica"]) -> Dict[str, List[Request]]:
        """Run one decode step on every live replica; homogeneous dense
        groups of >= fast_path_min pools sharing one params object go
        through fused jitted dispatches."""
        finished_by: Dict[str, List[Request]] = {}
        groups: Dict[Tuple[Any, ...], List["Replica"]] = {}
        for r in live:
            dp = r.decode_pool
            # device-bound pools fuse within their device, or across
            # devices under the mesh layout
            dev = None if self._mesh else dp.device
            sig = (dp.cfg.name, dp.params_token, dp.paged, dp.max_batch,
                   dp.max_seq_len, dev)
            groups.setdefault(sig, []).append(r)
        for sig, rs in groups.items():
            if not sig[2] and len(rs) >= self.fast_path_min:
                finished_by.update(self._decode_fused(sig, rs))
            else:
                for r in rs:
                    finished_by[r.name] = r.decode_pool.decode_once()
                    self.stats.serial_decode_calls += 1
        return finished_by

    def _decode_fused(self, sig, reps: List["Replica"]) -> Dict[str, List[Request]]:
        """Jitted steps over K homogeneous dense pools, in chunks of
        ``max_fused_group`` padded to powers of two with a repeat of the
        chunk's first pool (results discarded) so a drifting fleet rebuilds
        O(log fleet) programs, not one per group size. Each pool's key
        split, sampling and accounting are byte-for-byte the per-pool
        path's — only dispatch is shared.

        Two dispatch shapes per chunk:

        * ``batch_replicas`` (default) — ONE ``vmap``-batched program over
          replica-stacked buffers (``_decode_chunk_batched``). The stacked
          cache persists between steps in a ``CacheBank`` the member pools
          view through ``BankRow``s, so a stable group never re-stacks; an
          optional ``shard_map`` layout spreads the replica axis over the
          host's devices.
        * tuple path (``batch_replicas=False``) — the PR-7 program of K
          traced sub-calls (``_decode_chunk_tuple``), kept as the
          byte-identity baseline and opt-out.
        """
        st = self.stats
        pools = [r.decode_pool for r in reps]
        if self.batch_replicas:
            pres = [p._decode_begin(keep_view=True) for p in pools]
        else:
            pres = [p._decode_begin() for p in pools]
        finished: Dict[str, List[Request]] = {}
        for chunk in self._decode_chunks(pools):
            chunk_pools = [pools[j] for j in chunk]
            chunk_pres = [pres[j] for j in chunk]
            mesh = self._mesh and chunk_pools[0].device is not None
            # the group's padded size: a mesh group takes no padding
            p2 = len(chunk) if mesh else self._pow2(len(chunk))
            # one fused group: its dispatch, then each member's tokens to
            # the host and accounting (``decode.sync``/``decode.account``
            # inside it)
            with span("decode.fused", p2):
                if mesh:
                    outs = self._decode_chunk_mesh(sig, chunk_pools, chunk_pres)
                elif self.batch_replicas:
                    outs = self._decode_chunk_batched(sig, chunk_pools,
                                                      chunk_pres)
                else:
                    outs = self._decode_chunk_tuple(sig, chunk_pools, chunk_pres)
                st.fused_decode_calls += 1
                st.pad_waste += p2 - len(chunk_pools)
                for j, pre, out in zip(chunk, chunk_pres, outs):
                    finished[reps[j].name] = pools[j]._decode_finish(pre, *out)
        return finished

    def _decode_chunks(self, pools: List[Pool]) -> List[List[int]]:
        """Index chunks of a fused group: runs of ``max_fused_group``; for
        device-bound pools under the mesh layout, chunks of distinct
        devices in device order (one bank row per device, and a stable
        member order keeps the bank coherent between steps)."""
        n = len(pools)
        if not (self._mesh and pools[0].device is not None):
            return [list(range(i, min(i + self.max_fused_group, n)))
                    for i in range(0, n, self.max_fused_group)]
        chunks: List[List[int]] = []
        for j in sorted(range(n), key=lambda j: pools[j].device.id):
            for c in chunks:
                if (len(c) < self.max_fused_group
                        and all(pools[i].device != pools[j].device for i in c)):
                    c.append(j)
                    break
            else:
                chunks.append([j])
        return chunks

    def _decode_chunk_tuple(self, sig, pools: List[Pool],
                            pres: List[dict]) -> List[Any]:
        """The PR-7 fused program: K traced sub-calls over a tuple of
        per-pool argument tuples."""
        k = len(pools)
        p2 = self._pow2(k)
        args_list = [pre["args"][1:] for pre in pres]
        args_list.extend([args_list[0]] * (p2 - k))
        pool0 = pools[0]

        def build(pool0=pool0):
            impl = pool0._decode_impl   # pure in cfg; shared across group

            def make():
                def fused(params, per_pool):
                    return tuple(impl(params, *args) for args in per_pool)

                return jax.jit(fused)

            return _program(("decode", impl), make)

        fn = self._fused_fn(("decode", sig, p2), build)
        outs = fn(pool0.params, tuple(args_list))
        return list(outs[:k])

    def _bank_coherent(self, pools: List[Pool], p2: int) -> Optional[CacheBank]:
        """The chunk's persistent stacked bank, if every member still views
        row i of ONE bank of exactly this pow2 size — the condition under
        which last step's donated output tree IS this step's input stack."""
        c0 = pools[0].cache
        if not isinstance(c0, BankRow) or c0.bank.size != p2 or c0.index != 0:
            return None
        bank = c0.bank
        for j, p in enumerate(pools[1:], start=1):
            c = p.cache
            if not isinstance(c, BankRow) or c.bank is not bank or c.index != j:
                return None
        return bank

    def _bank_rows_common(self, pools: List[Pool]):
        """(bank, row indices) if every member views SOME row of one common
        bank — any order, any pow2 size. The membership-churn shape: last
        step's group shrank/grew/reordered, so the rows are all still on one
        device-resident bank, just not at identity positions."""
        c0 = pools[0].cache
        if not isinstance(c0, BankRow):
            return None
        bank = c0.bank
        idx = [c0.index]
        for p in pools[1:]:
            c = p.cache
            if not isinstance(c, BankRow) or c.bank is not bank:
                return None
            idx.append(c.index)
        return bank, idx

    def _decode_chunk_batched(self, sig, pools: List[Pool],
                              pres: List[dict]) -> List[Any]:
        """ONE batched program per chunk: dense decode args stack along a
        leading replica axis (pow2-padded with repeats of member 0) and run
        through ``vmap_replicas`` (or ``shard_map_replicas``).

        Fast path — the group's caches already live as rows of one
        ``CacheBank`` from the previous step: the bank's stacked tree feeds
        the program directly (donated; the output tree replaces it), so a
        stable group pays ZERO stack/unstack work per step. Gather path —
        the member set churned but every row still lives on ONE bank: an
        index-array gather INSIDE the program re-stacks them (one dispatch,
        no host materialise; the source bank is left intact for pools that
        left the group). Slow path — rows scattered across banks or dense
        trees (first fused step, group merge): each pool materialises its
        row and the program stacks the K rows INSIDE jit into a fresh bank.

        RNG keys ride as a (P,)-tuple pytree and stack inside jit; small
        host args (tokens/lengths/active/temps) stack as numpy. Outputs come
        back stacked; ``next_tok``/``lengths`` cross to the host as ONE
        (P, B) transfer each, and every member's cache becomes a ``BankRow``
        of the (new) bank — per-pool values byte-identical to the tuple
        path's (vmap over independent rows is a layout change, not a math
        change)."""
        st = self.stats
        k = len(pools)
        p2 = self._pow2(k)
        pad = p2 - k
        # dense _decode_begin args: (params, toks, cache, lengths, active,
        # key, temps) — stack everything but params/cache as host numpy
        toks, lengths, active, keys, temps = _stack_decode_args(pres, pad)
        pool0 = pools[0]
        devs = _host_mesh(self.batch_layout, p2)
        if self.batch_layout == "shard_map":
            if devs is None:
                st.vmap_fallbacks += 1
            else:
                st.shard_map_calls += 1
        bank = self._bank_coherent(pools, p2)

        if bank is not None:
            fn = self._fused_fn(("decode", sig, p2), lambda: _bank_step_program(
                pool0._decode_impl, devs, p2))
            next_tok, new_tree, new_lengths = fn(
                pool0.params, bank.tree, toks, lengths, active, keys, temps)
            bank.tree = new_tree
        elif (common := self._bank_rows_common(pools)) is not None:
            src, idx = common
            rows_idx = np.asarray(idx + [idx[0]] * pad, dtype=np.int32)

            def build(pool0=pool0, src_size=src.size):
                impl = pool0._decode_impl

                def make():
                    def fused(params, src_tree, rows, toks, lengths, active,
                              keys, temps):
                        cache = jax.tree.map(lambda x: x[rows], src_tree)
                        kstack = jnp.stack(keys)
                        core = _batched_core(impl, devs)
                        return core(params, toks, cache, lengths, active,
                                    kstack, temps)

                    # no donation: pools that left the group still view
                    # rows of the source bank
                    return jax.jit(fused)

                return _program(
                    ("decode_batched_gather", impl, devs, p2, src_size),
                    make)

            fn = self._fused_fn(("decode_gather", sig, p2, src.size), build)
            next_tok, new_tree, new_lengths = fn(
                pool0.params, src.tree, rows_idx, toks, lengths, active,
                keys, temps)
            bank = CacheBank(new_tree, p2)
            st.bank_gathers += 1
        else:
            # re-stack: materialise the member rows — ONE multi-row gather
            # per source bank (never one per member; a group merge touches
            # 2-3 banks, not K rows), dense trees are already rows — and
            # stack INSIDE the program
            sources: List[Tuple[CacheBank, List[Pool]]] = []
            for p in pools:
                c = p.cache
                if isinstance(c, BankRow):
                    for ent in sources:
                        if ent[0] is c.bank:
                            ent[1].append(p)
                            break
                    else:
                        sources.append((c.bank, [p]))
            for src, members in sources:
                if len(members) == 1:
                    members[0].materialize_cache()
                    continue
                n = len(members)
                idx = np.asarray([p.cache.index for p in members],
                                 dtype=np.int32)

                def make(n=n):
                    def take(tree, rows_ix):
                        sub = jax.tree.map(lambda x: x[rows_ix], tree)
                        return tuple(jax.tree.map(lambda x, i=i: x[i], sub)
                                     for i in range(n))

                    return jax.jit(take)

                rows_trees = _program(("bank_rows_take", n), make)(
                    src.tree, idx)
                members[0].jit_dispatches += 1
                for p, rt in zip(members, rows_trees):
                    p.cache = rt
            rows = tuple(p.cache for p in pools) + (pool0.cache,) * pad

            def build(pool0=pool0):
                impl = pool0._decode_impl

                def make():
                    def fused(params, rows, toks, lengths, active, keys,
                              temps):
                        cache = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
                        kstack = jnp.stack(keys)
                        core = _batched_core(impl, devs)
                        return core(params, toks, cache, lengths, active,
                                    kstack, temps)

                    # no donation: pad rows alias row 0
                    return jax.jit(fused)

                return _program(("decode_batched_restack", impl, devs, p2),
                                make)

            fn = self._fused_fn(("decode_restack", sig, p2), build)
            next_tok, new_tree, new_lengths = fn(
                pool0.params, rows, toks, lengths, active, keys, temps)
            bank = CacheBank(new_tree, p2)
            st.bank_rebuilds += 1
        st.batched_decode_calls += 1
        # one host transfer per stacked output, then row views per pool
        next_np = np.asarray(next_tok)
        len_np = np.asarray(new_lengths)
        return [(next_np[j], BankRow(bank, j), len_np[j])
                for j in range(k)]

    def _decode_chunk_mesh(self, sig, pools: List[Pool],
                           pres: List[dict]) -> List[Any]:
        """ONE shard_map program for device-bound pools on distinct
        devices: the mesh is exactly their devices, and row j of the
        stacked bank lives on pool j's device. No padding: the mesh takes
        the group's size. A coherent bank (same members as the last step)
        is donated and replaced as on one device; otherwise the bank is
        assembled from where each row already lives — the member's shard
        of an earlier bank (no copy) or its own dense cache — so no row and
        no weight ever crosses devices. The weights are the members'
        per-device copies, assembled into one replicated array."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        st = self.stats
        k = len(pools)
        devs = tuple(p.device for p in pools)
        mesh = Mesh(np.asarray(devs), ("replica",))
        toks, lengths, active, keys, temps = _stack_decode_args(pres, 0)
        pool0 = pools[0]
        bank = self._bank_coherent(pools, k)
        if bank is None:
            rows = [self._mesh_row(p) for p in pools]
            stacked = NamedSharding(mesh, PartitionSpec("replica"))
            bank = CacheBank(jax.tree.map(
                lambda *xs: jax.make_array_from_single_device_arrays(
                    (k,) + xs[0].shape[1:], stacked, list(xs)), *rows), k)
            st.bank_rebuilds += 1
        pkey = (pool0.params_token, devs)
        params = self._mesh_params.get(pkey)
        if params is None:
            replicated = NamedSharding(mesh, PartitionSpec())
            params = self._mesh_params[pkey] = jax.tree.map(
                lambda *xs: jax.make_array_from_single_device_arrays(
                    xs[0].shape, replicated, list(xs)),
                *[p.params for p in pools])
        fn = self._fused_fn(("decode_mesh", sig, devs), lambda: _bank_step_program(
            pool0._decode_impl, devs, k))
        next_tok, bank.tree, new_lengths = fn(
            params, bank.tree, toks, lengths, active, keys, temps)
        st.batched_decode_calls += 1
        st.shard_map_calls += 1
        next_np = np.asarray(next_tok)
        len_np = np.asarray(new_lengths)
        return [(next_np[j], BankRow(bank, j), len_np[j])
                for j in range(k)]

    @staticmethod
    def _mesh_row(pool: Pool):
        """A bound pool's cache as a one-row stack on its own device: its
        shard of the mesh bank it last stepped in (mesh banks hold one row
        per device), or its dense cache grown a leading axis — donated, so
        the row takes over the cache's buffer instead of a second copy."""
        c = pool.cache
        if isinstance(c, BankRow):
            return jax.tree.map(
                lambda x: row_on_device(x, c.index, pool.device)[0], c.bank.tree)
        return _program(("row_expand",), lambda: jax.jit(
            lambda t: jax.tree.map(lambda x: x[None], t), donate_argnums=(0,)))(c)

    # ------------------------------------------------------ warm / autoscaler
    def _schedule_warm(self, r: "Replica"):
        key = (r.name, r._warming_until_s)
        if key not in self._warm_sched:
            self._warm_sched.add(key)
            self._push(r._warming_until_s, PRIO_WARM, "warm", r.name)

    def _handle_warm(self, r: "Replica", t: float):
        self._warm_sched.discard((r.name, t))
        if not r.powered or r._warming_until_s is None:
            return                          # powered down / already warm
        if t < r._warming_until_s - _EPS:
            self._schedule_warm(r)          # window moved; fire later
            return
        for p in r.pools().values():        # warm-up idle watts accrue
            self._catch_up(p, t)
        r._warming_until_s = None
        self.fleet._record_scale(t, "warm", r, "warm-up window elapsed")
        if self._admit_sched[r.name] == 0:
            self._admit(r, t, accrue=True)
            self._after_admit(r)

    def _handle_scale(self, t: float):
        fleet = self.fleet
        for r in fleet.replicas:            # queue ages measure against t
            r.advance_all(t)
        self._autoscale()
        if self._real > 0 or fleet.busy():
            self._push(t + self._tick_interval, PRIO_SCALE, "scale", None)

    def _autoscale(self):
        fleet = self.fleet
        fleet._autoscale()
        for r in fleet.replicas:
            if r.powered and r._warming_until_s is not None:
                self._schedule_warm(r)
