"""Phase pool: the slot/cache machinery one serving phase runs on.

A ``Pool`` owns the JAX-side state the old monolithic engine carried —
slot pool, KV/state cache, jitted prefill/decode/scatter — plus the
energy-side state the disaggregated cluster needs:

* ``PhaseStats`` with per-phase joules and the configured-vs-actual clock
  of the lever currently applied to this pool (the paper's Table 1 gap);
* a mutable power gauge + ``PowerSampler`` (repro.core.metering) so each
  pool is metered exactly like the paper meters a device: 50 ms polling of
  the pool's *current* operating point;
* an ``OperatingPoint`` slot written by a ClockController — the pool itself
  never picks clocks, it only accounts at whatever point it was put.

Two cache layouts:

* **dense** (the seed layout) — one stacked ``(B, max_len, ...)`` row per
  slot, preallocated. Admission is slot-bound.
* **paged** (``paged=True``) — per-token caches live in fixed-size token
  blocks (``repro.serving.paged_cache.BlockAllocator``) shared by all
  slots through per-slot block tables; O(1) recurrent state stays slot
  indexed. Admission is *block*-bound (continuous batching: admit whenever
  blocks are free), growth allocates a block at a time, and exhaustion
  preempts the youngest slot (recompute-style eviction: the request is
  reset and requeued). Every block touched per decode step increments the
  pool's ``TrafficCounter``, and when a controller has attached an
  operating point, per-request decode joules are derived from those
  measured bytes (``repro.core.energy.joules_from_hbm_traffic``) instead
  of the shape-based energy/token estimate.

Two clocks (``repro.core.clock``):

* **wall** (the default ``time.perf_counter``) — the seed behaviour,
  token-identical to before the virtual-time refactor.
* **virtual** (pass a ``VirtualClock``) — the pool *advances* the clock by
  the modelled duration of each phase call (``op.profile.t_total`` at its
  live operating point) and meters energy synchronously (no sampler
  thread), so trace replays are deterministic and DVFS decisions feed back
  into simulated TTFT/TBT. Requires a ClockController to supply operating
  points; without one virtual time simply never advances.

Every request carries a ``LatencyLedger`` stamped here on the serving
clock — arrival (by the cluster/engine), admitted (prefill start), first
token (placement), every decode token, finish — from which TTFT and
per-step TBT derive in both clock modes.

JAX-shape discipline is unchanged from the seed engine: decode runs one
jitted step over ALL slots (static batch, per-slot lengths, active mask);
prefill runs batch-1 with prompt lengths padded to power-of-2 buckets, and
the filled cache row is scattered into a slot — in the cluster that scatter
IS the prefill->decode migration (for a paged pool: a block-table handoff
plus one jitted page scatter, the copy-on-migrate).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.clock import VirtualClock
from repro.core.dvfs import OperatingPoint
from repro.core.energy import joules_from_hbm_traffic
from repro.core.latency import LatencyLedger
from repro.core.metering import GaugeSource, PowerSampler
from repro.core.workload import weight_stream_bytes
from repro.models import (
    decode_step,
    decode_step_paged,
    init_cache,
    init_paged_cache,
    kv_cache_bytes_per_token,
    paged_layout,
    prefill,
    prefill_suffix,
    recurrent_state_bytes,
)
from repro.models.attention import decode_rows_read
from repro.models.config import ModelConfig
from repro.serving.paged_cache import NULL_PAGE, BlockAllocator, TrafficCounter
from repro.serving.prefix import PrefixHit, PrefixIndex, PrefixStats
from repro.serving.spans import span

# Attention paradigms whose KV rows depend only on their own prefix — the
# precondition for sharing cached pages across requests. Recurrent/MoE-state
# blocks carry slot-indexed O(1) state that is NOT position-addressable, so
# a pool holding any other kind refuses prefix sharing loudly.
SHAREABLE_KINDS = ("attn", "attn_global", "shared_attn")

# Back-compat default: seed code stopped on token id 0. The real stop id now
# comes from ``ModelConfig.eos_token_id`` (per-request override on Request).
EOS = 0


# ---------------------------------------------------------------------------
# Shared jitted-callable cache. ``ModelConfig`` is a value-equal, hashable
# dataclass, so every pool running the same config shares ONE traced program
# per (kind, static-shape) key instead of compiling per pool — at 100
# homogeneous replicas that turns 100 prefill + 100 scatter + 100 decode
# compiles into one of each. The cached callables are pure functions of their
# arguments (config and shape constants enter by closure FROM THE KEY), so
# sharing cannot couple pool state.
#
# The cache is a capped LRU, not a bare dict: the cached closures retain
# whatever they close over, and a long pytest session or a benchmark sweep
# that builds hundreds of fleet shapes would otherwise hold every program
# (and transitively every XLA executable) ever compiled. Live pools keep
# strong references to the callables they fetched, so eviction only drops
# programs no current pool holds.
_JIT_CACHE_CAP = 256
_JIT_CACHE: "OrderedDict[Tuple, Any]" = OrderedDict()


def _cached(key: Tuple, build: Callable[[], Any]) -> Any:
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = _JIT_CACHE[key] = build()
        while len(_JIT_CACHE) > _JIT_CACHE_CAP:
            _JIT_CACHE.popitem(last=False)
    else:
        _JIT_CACHE.move_to_end(key)
    return fn


def clear_program_caches() -> None:
    """Drop every process-wide jitted-program cache: the per-pool
    ``_JIT_CACHE`` here and the event engine's fused ``_PROGRAM_CACHE``.
    Benchmark sweeps call this between sweep points so each point pays its
    own compiles instead of riding (and retaining) the previous point's;
    live pools keep the callables they already fetched, so clearing never
    breaks an engine mid-replay — the next fetch just rebuilds."""
    _JIT_CACHE.clear()
    from repro.serving import events as _events
    _events._PROGRAM_CACHE.clear()


# ---------------------------------------------------------------------------
# Stable params identity. Fused-dispatch group signatures need "same weights"
# as a hashable token that (unlike ``id(params)``) can never be recycled onto
# a different pool's weights by the allocator after a GC. Tokens are drawn
# from one monotonic counter; the registry is a small LRU of live params
# pytrees (plain dicts are not weakref-able) so repeated pool constructions
# over the same object share a token without the registry pinning every
# params ever seen. An evicted-and-re-registered params gets a FRESH token —
# the failure mode is a missed fusion, never a wrong grouping.
_PARAMS_TOKEN_CAP = 64
_PARAMS_TOKENS: "OrderedDict[int, Tuple[Any, int]]" = OrderedDict()
_params_token_counter = itertools.count(1)


def params_token_for(params: Any) -> int:
    """The stable monotonic token for this exact params object."""
    ent = _PARAMS_TOKENS.get(id(params))
    if ent is not None and ent[0] is params:
        _PARAMS_TOKENS.move_to_end(id(params))
        return ent[1]
    tok = next(_params_token_counter)
    _PARAMS_TOKENS[id(params)] = (params, tok)
    while len(_PARAMS_TOKENS) > _PARAMS_TOKEN_CAP:
        _PARAMS_TOKENS.popitem(last=False)
    return tok


def prefill_impl_for(cfg: ModelConfig, max_seq_len: int):
    """The unjitted batch-1 bucketed prefill body for (cfg, max_seq_len) —
    also the building block the event engine's fused admission prefill
    traces K times into one program."""
    def build():
        def prefill_impl(params, tokens, true_len, bucket):
            cache1 = init_cache(cfg, 1, max_seq_len)
            logits, cache1, _ = prefill(
                params, cfg, tokens, cache1, prompt_lengths=true_len
            )
            return logits, cache1
        return prefill_impl
    return _cached(("prefill_impl", cfg, max_seq_len), build)


def decode_impl_for(cfg: ModelConfig):
    """The unjitted one-step dense decode body for ``cfg`` (the event
    engine's fused decode traces it once per pool in a group)."""
    def build():
        def decode_impl(params, tokens, cache, lengths, active, key, temperature):
            logits, new_cache, new_lengths = decode_step(
                params, cfg, tokens, cache, lengths, active=active)
            next_tok = Pool._sample(logits, key, temperature)
            new_lengths = jnp.where(active, new_lengths, lengths)
            return next_tok, new_cache, new_lengths
        return decode_impl
    return _cached(("decode_impl", cfg), build)


def decode_paged_impl_for(cfg: ModelConfig):
    def build():
        def decode_paged_impl(params, tokens, cache, lengths, active, tables,
                              key, temperature):
            logits, new_cache, new_lengths = decode_step_paged(
                params, cfg, tokens, cache, lengths, active, tables)
            next_tok = Pool._sample(logits, key, temperature)
            new_lengths = jnp.where(active, new_lengths, lengths)
            return next_tok, new_cache, new_lengths
        return decode_paged_impl
    return _cached(("decode_paged_impl", cfg), build)


def decode_jit_for(cfg: ModelConfig, paged: bool = False):
    """The jitted one-step decode program a pool dispatches on its serial
    path (dense or paged). The cache argument is donated: the step writes
    the new cache over the old one instead of holding two copies, which
    at full width is the difference between fitting one chip or not."""
    if paged:
        return _cached(("decode_paged_jit", cfg), lambda: jax.jit(
            decode_paged_impl_for(cfg), donate_argnums=(2,)))
    return _cached(("decode_jit", cfg), lambda: jax.jit(
        decode_impl_for(cfg), donate_argnums=(2,)))


def _carry_tokens_impl(prev, host, carried):
    """A step's input tokens when the step before it is still in flight:
    slots it serves take its device output, fresh placements the host's."""
    return jnp.where(carried, prev, host)


def params_on_device(params: Any, device: Any) -> Any:
    """The copy of ``params`` that ``device`` holds, without copying: each
    leaf must be committed to ``device`` or replicated over devices that
    include it (``jax.device_put`` onto a replicated ``NamedSharding``, or
    ``init_params_jit`` with one). ``device=None`` returns ``params``."""
    if device is None:
        return params

    def local(x):
        for shard in getattr(x, "addressable_shards", ()):
            if shard.device == device and shard.data.shape == x.shape:
                return shard.data
        raise ValueError(
            f"a weight of shape {getattr(x, 'shape', None)} has no whole copy "
            f"on {device}: replicate the params over the fleet's devices")

    return jax.tree.map(local, params)


def row_on_device(leaf: jax.Array, row: int, device: Any) -> Tuple[jax.Array, int]:
    """(single-device array, offset) holding replica row ``row`` of a
    stacked bank leaf on ``device`` — the whole leaf for a one-device bank,
    that device's shard for a bank sharded over the replica axis."""
    for shard in leaf.addressable_shards:
        if shard.device != device:
            continue
        sl = shard.index[0] if shard.index else slice(None)
        start = sl.start or 0
        stop = leaf.shape[0] if sl.stop is None else sl.stop
        if start <= row < stop:
            return shard.data, row - start
    raise ValueError(f"bank row {row} is not on {device}")


def _scatter_impl(big_cache, small_cache, slot):
    # stage-cache leaves are stacked (n_units, B, ...): batch axis is 1
    return jax.tree.map(
        lambda big, small: jax.lax.dynamic_update_slice_in_dim(
            big, small, slot, axis=1),
        big_cache,
        small_cache,
    )


def _multi_scatter_impl(big_cache, small_caches, slots):
    """K batch-1 rows scattered into K slots in ONE traced program — the
    event engine's ``_flush`` places a whole admission wave per dispatch.
    Updates chain in order, so padding (a repeat of row 0 into slot 0) is
    idempotent, not just inert."""
    for small, slot in zip(small_caches, slots):
        big_cache = _scatter_impl(big_cache, small, slot)
    return big_cache


# ---------------------------------------------------------------------------
# Replica-batched cache state. The event engine's batched fused decode keeps
# the K pools of one fused group stacked along a leading replica axis in a
# single device pytree, so each step is ONE vmapped program over the stack
# instead of K traced sub-calls — and, crucially, the stack persists between
# steps (re-stacking K caches every step would cost more than the fusion
# saves). ``CacheBank`` is the mutable holder of that stacked pytree;
# ``BankRow`` is what a member pool stores in ``self.cache`` between steps: a
# (bank, row) view. All reads go THROUGH the bank, so the fast path can
# donate ``bank.tree`` to XLA and swap in the output without invalidating any
# member's view. A pool that needs its own dense row again (serial decode,
# tuple-path fusion) materialises it with one jitted gather.


class CacheBank:
    """Stacked cache pytree for one batched fused-decode group: every leaf
    carries a leading replica axis of ``size`` rows (pow2-padded; pad rows
    hold inert repeats and are never read back)."""

    __slots__ = ("tree", "size")

    def __init__(self, tree: Any, size: int):
        self.tree = tree
        self.size = size


class BankRow:
    """A pool's between-steps view into a ``CacheBank``: row ``index`` of
    ``bank.tree``. Opaque to accounting code — only the batched engine path
    and the pool's materialise/scatter helpers look inside."""

    __slots__ = ("bank", "index")

    def __init__(self, bank: CacheBank, index: int):
        self.bank = bank
        self.index = index


def _bank_row_impl(tree, row):
    """Gather one replica row out of a stacked bank (materialisation)."""
    return jax.tree.map(lambda x: x[row], tree)


def _bank_scatter_impl(tree, small_cache, row, slot):
    """Scatter a batch-1 prefilled cache row into slot ``slot`` of replica
    row ``row`` of a stacked bank — the write-through twin of
    ``_scatter_impl`` for pools whose cache currently lives in a bank.
    Stacked leaves are (K, n_units, B, ...); the batch-1 row lands at
    ``[row, :, slot]``."""
    def scat(big, small):
        start = (row, 0, slot) + (0,) * (big.ndim - 3)
        return jax.lax.dynamic_update_slice(big, small[None].astype(big.dtype),
                                            start)
    return jax.tree.map(scat, tree, small_cache)


def _bank_multi_scatter_impl(tree, small_caches, row, slots):
    """K batch-1 rows into K slots of ONE replica row of a bank, chained in
    order (padding repeats row 0 into slot 0, idempotent like the dense
    multi-scatter)."""
    for small, slot in zip(small_caches, slots):
        tree = _bank_scatter_impl(tree, small, row, slot)
    return tree


# -------------------------------------------------------- queue primitives
def popleft(waiting) -> "Request":
    """Pop the queue head from a deque (O(1)) or a list (legacy O(n)) —
    the one admission-queue pop used by scheduler/engine/validator code so
    deque-backed queues and user-supplied lists both work."""
    if isinstance(waiting, deque):
        return waiting.popleft()
    return waiting.pop(0)


def requeue_front(waiting, evicted: Sequence["Request"]) -> None:
    """Put preempted requests back at the queue head (oldest first), on a
    deque or a list alike."""
    if not evicted:
        return
    if isinstance(waiting, deque):
        waiting.extendleft(reversed(evicted))
    else:
        waiting[:0] = evicted


@dataclasses.dataclass(slots=True)
class Request:
    uid: int
    prompt: np.ndarray                     # (L,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_token_id: Optional[int] = None     # None -> the pool's ModelConfig id
    bucket: str = "mixed"                  # trace length-bucket tag (routing)
    replica: Optional[str] = None          # fleet replica that served it
    # filled by the pool/scheduler
    output: List[int] = dataclasses.field(default_factory=list)
    prefill_s: float = 0.0
    decode_s: float = 0.0
    prefill_j: float = 0.0                 # modelled joules at the pool's op
    decode_j: float = 0.0
    decode_read_bytes: int = 0             # paged pools: measured HBM traffic
    decode_write_bytes: int = 0
    preemptions: int = 0                   # times evicted + restarted
    prefix_tokens: int = 0                 # prompt positions served from shared pages
    saved_prefill_j: float = 0.0           # prefill joules sharing avoided (side-channel:
                                           # NOT part of energy_j — conservation holds)
    done: bool = False
    # event ledger (arrival/admitted/first-token/finish + per-token stamps),
    # stamped by the pool on the serving clock — wall or virtual alike
    ledger: LatencyLedger = dataclasses.field(default_factory=LatencyLedger)

    @property
    def energy_j(self) -> float:
        return self.prefill_j + self.decode_j

    @property
    def decode_bytes(self) -> int:
        return self.decode_read_bytes + self.decode_write_bytes

    @property
    def ttft_s(self) -> Optional[float]:
        return self.ledger.ttft_s

    @property
    def tbt_s(self) -> List[float]:
        return self.ledger.tbt_s

    @property
    def e2e_s(self) -> Optional[float]:
        return self.ledger.e2e_s


# ------------------------------------------------------- request freelist
# Replaying 10^6 requests builds (and drops) 10^6 Request + LatencyLedger
# pairs; the freelist recycles them once a streaming consumer (the event
# engine's ``on_finish`` hook) is done with one, keeping the hot loop
# allocation-free and replay memory flat. ``slots=True`` on both classes
# makes the recycled instances cheap to reset field-by-field.
_REQUEST_FREELIST: List[Request] = []
_REQUEST_FREELIST_CAP = 8192


def acquire_request(uid: int, prompt: np.ndarray, *, max_new_tokens: int = 32,
                    temperature: float = 0.0,
                    eos_token_id: Optional[int] = None,
                    bucket: str = "mixed",
                    replica: Optional[str] = None) -> Request:
    """A fresh-looking Request, recycled from the freelist when one is
    available (fields fully reset by ``release_request``)."""
    if _REQUEST_FREELIST:
        req = _REQUEST_FREELIST.pop()
        req.uid = uid
        req.prompt = prompt
        req.max_new_tokens = max_new_tokens
        req.temperature = temperature
        req.eos_token_id = eos_token_id
        req.bucket = bucket
        req.replica = replica
        return req
    return Request(uid=uid, prompt=prompt, max_new_tokens=max_new_tokens,
                   temperature=temperature, eos_token_id=eos_token_id,
                   bucket=bucket, replica=replica)


def release_request(req: Request) -> None:
    """Return a FINISHED request to the freelist. The caller promises to
    hold no further references: output, ledger stamps and energy fields are
    wiped here so the next ``acquire_request`` hands out a blank."""
    if len(_REQUEST_FREELIST) >= _REQUEST_FREELIST_CAP:
        return
    req.output = []
    req.prefill_s = req.decode_s = 0.0
    req.prefill_j = req.decode_j = 0.0
    req.decode_read_bytes = req.decode_write_bytes = 0
    req.preemptions = 0
    req.prefix_tokens = 0
    req.saved_prefill_j = 0.0
    req.done = False
    req.ledger.reset()
    _REQUEST_FREELIST.append(req)


@dataclasses.dataclass
class PhaseStats:
    prefill_tokens: int = 0
    prefill_s: float = 0.0
    prefill_calls: int = 0
    decode_tokens: int = 0
    decode_s: float = 0.0
    decode_steps: int = 0
    # energy attribution at the pool's operating point (0 when unmetered)
    prefill_j: float = 0.0
    decode_j: float = 0.0
    # block-level HBM traffic behind decode_j (0 on dense/unmetered pools)
    decode_read_bytes: int = 0
    decode_write_bytes: int = 0
    # dense decode steps: cache rows the attention streams, of the B x L
    # rows each step holds (the TPU kernel's block-rounded rows of the
    # active slots; every row on the XLA path)
    decode_kv_rows_read: int = 0
    decode_kv_rows_held: int = 0
    # lever state last applied to the pool that produced these stats
    configured_clock_mhz: float = 0.0
    actual_clock_mhz: float = 0.0
    lever_engaged: bool = False

    def merge_prefill(self, tokens: int, secs: float, joules: float = 0.0):
        self.prefill_tokens += tokens
        self.prefill_s += secs
        self.prefill_calls += 1
        self.prefill_j += joules

    def merge_decode(self, tokens: int, secs: float, joules: float = 0.0,
                     read_bytes: int = 0, write_bytes: int = 0,
                     kv_rows_read: int = 0, kv_rows_held: int = 0):
        self.decode_tokens += tokens
        self.decode_s += secs
        self.decode_steps += 1
        self.decode_j += joules
        self.decode_read_bytes += read_bytes
        self.decode_write_bytes += write_bytes
        self.decode_kv_rows_read += kv_rows_read
        self.decode_kv_rows_held += kv_rows_held

    def note_operating_point(self, op: OperatingPoint):
        self.actual_clock_mhz = float(op.actual_clock_mhz)
        # OperatingPoint.clock_gap_mhz owns the "configured is only MHz for
        # locks" rule; don't reimplement it here
        self.configured_clock_mhz = self.actual_clock_mhz + op.clock_gap_mhz
        self.lever_engaged = bool(op.engaged)

    @property
    def clock_gap_mhz(self) -> float:
        """Configured-vs-actual lock gap (the §5.2 'double disguise')."""
        return self.configured_clock_mhz - self.actual_clock_mhz

    @property
    def energy_j(self) -> float:
        return self.prefill_j + self.decode_j

    @property
    def decode_bytes(self) -> int:
        return self.decode_read_bytes + self.decode_write_bytes

    def merged_with(self, other: "PhaseStats") -> "PhaseStats":
        """Fieldwise token/time/energy sum; clock fields keep ``self``'s."""
        return PhaseStats(
            prefill_tokens=self.prefill_tokens + other.prefill_tokens,
            prefill_s=self.prefill_s + other.prefill_s,
            prefill_calls=self.prefill_calls + other.prefill_calls,
            decode_tokens=self.decode_tokens + other.decode_tokens,
            decode_s=self.decode_s + other.decode_s,
            decode_steps=self.decode_steps + other.decode_steps,
            prefill_j=self.prefill_j + other.prefill_j,
            decode_j=self.decode_j + other.decode_j,
            decode_read_bytes=self.decode_read_bytes + other.decode_read_bytes,
            decode_write_bytes=self.decode_write_bytes + other.decode_write_bytes,
            decode_kv_rows_read=self.decode_kv_rows_read + other.decode_kv_rows_read,
            decode_kv_rows_held=self.decode_kv_rows_held + other.decode_kv_rows_held,
            configured_clock_mhz=self.configured_clock_mhz,
            actual_clock_mhz=self.actual_clock_mhz,
            lever_engaged=self.lever_engaged,
        )


def _bucket(n: int, buckets=(32, 64, 128, 256, 512, 1024, 2048, 4096)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return int(2 ** np.ceil(np.log2(n)))


def head_validator(waiting: List[Request], pool: "Pool") -> Callable[[], Request]:
    """The single admission-validation path, shared by ``Scheduler.tick``
    and ``ServingEngine._admit``: returns a closure that validates the
    current queue head exactly once, dropping a poison request (one that
    could never be served, so admission gates would stay closed forever and
    livelock the queue) before the error surfaces."""
    validated: Optional[Request] = None

    def validated_head() -> Request:
        nonlocal validated
        req = waiting[0]
        if req is not validated:
            try:
                pool.validate(req)
            except ValueError:
                popleft(waiting)
                raise
            validated = req
        return req

    return validated_head


def observe_latencies(controller, pool: "Pool", admitted: List[Request],
                      finished: List[Request]) -> None:
    """Feed one step's measured latencies back to the controller — the slo
    mode's closed loop, shared by ``Cluster.step`` and
    ``ServingEngine.step``: TTFT of everything admitted this tick, plus the
    inter-token gap every request (still live or just finished) saw from
    this decode step."""
    live = [r for r in pool.slot_req if r is not None]
    controller.observe(
        ttft_s=[r.ledger.ttft_s for r in admitted
                if r.ledger.ttft_s is not None],
        tbt_s=[t for r in live + finished
               if (t := r.ledger.last_tbt_s) is not None],
    )


class Pool:
    """Slot pool + jitted model calls + phase/energy accounting for one phase."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        *,
        role: str = "decode",              # "prefill" | "decode"
        max_batch: int = 8,
        max_seq_len: int = 4096,
        rng_seed: int = 0,
        clock: Callable[[], float] = time.perf_counter,
        meter_interval_s: float = 0.050,
        paged: bool = False,
        kv_block_size: int = 16,
        kv_blocks: Optional[int] = None,   # default: dense-equivalent budget
        prefix_sharing: bool = False,
        device: Any = None,
        overlap: bool = False,
    ):
        self.cfg = cfg
        # device binding (multi-device hosts): a bound pool keeps its cache
        # on ``device`` and runs its own programs against the copy of the
        # weights that device holds; ``None`` leaves placement to JAX (the
        # default device). See ``params_on_device``.
        self.device = device
        self.params = params_on_device(params, device)
        # stable weights-identity token for fused-dispatch grouping: pools
        # constructed over the SAME params object share it — per-device
        # views of one replicated params included; a freed-and-rebuilt
        # fleet can never collide with this one (monotonic counter, never
        # recycled — unlike id(params))
        self.params_token = params_token_for(params)
        self.role = role
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len
        self.clock = clock
        # virtual mode: the clock only moves when this pool advances it by
        # the modelled duration of each phase call (needs an operating
        # point, i.e. a ClockController); metering goes synchronous.
        self.virtual = isinstance(clock, VirtualClock)
        # overlap: ``decode_once`` dispatches step N+1 before it reads step
        # N's tokens, so the host's accounting, admission and dispatch run
        # while the device computes. Dense wall-clock pools only: modelled
        # time has nothing to hide, and a paged step's block tables need
        # the lengths the host has not read yet.
        self.overlap = overlap and not self.virtual and not paged
        self._inflight: Optional[dict] = None
        self._settled_s = float("-inf")
        self.stats = PhaseStats()
        self.eos_token_id = cfg.eos_token_id

        # energy side: operating point is written by a ClockController; the
        # gauge feeds this pool's sampler so the metering stack sees the
        # modelled power of whatever point the pool currently runs at, or
        # the idle floor while the pool has no work.
        self.op: Optional[OperatingPoint] = None
        self.prefill_op: Optional[OperatingPoint] = None
        self.idle_power_w: float = 0.0
        self.hbm_bw_eff: float = 0.0       # set by the controller; enables
                                           # traffic-derived decode joules
        self.gauge = GaugeSource(0.0)
        self.sampler = PowerSampler(
            self.gauge, interval_s=meter_interval_s, clock=clock,
            synchronous=self.virtual,
        )
        self._in_phase_call = False
        self._metering_active = False
        self._measured_j_total = 0.0

        # decode-slot arrays allocate lazily on first placement, so a
        # prefill-role pool never holds an unused stacked KV cache
        self.cache = None
        self.lengths = None
        self.cur_token = None
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.peak_occupancy = 0
        self._key = jax.random.PRNGKey(rng_seed)

        # paged-cache side: allocator + per-slot block tables (host side;
        # only the stacked (B, nb) table array enters jit)
        self.paged = paged
        self.kv_block_size = kv_block_size
        self.allocator: Optional[BlockAllocator] = None
        self.traffic = TrafficCounter()
        self.evicted: List[Request] = []
        if paged:
            if max_seq_len % kv_block_size:
                raise ValueError(
                    f"max_seq_len {max_seq_len} not a multiple of "
                    f"kv_block_size {kv_block_size}"
                )
            n_blocks = kv_blocks if kv_blocks is not None else (
                max_batch * max_seq_len // kv_block_size
            )
            self.allocator = BlockAllocator(n_blocks, kv_block_size)
            nb_per_slot = max_seq_len // kv_block_size
            self.block_tables = np.zeros((max_batch, nb_per_slot), np.int32)
            self._layout = paged_layout(cfg)
            # byte-accuracy constants (per token / per request / per step)
            self._kv_token_bytes = kv_cache_bytes_per_token(cfg)
            self._state_read_bytes = recurrent_state_bytes(cfg)
            self._state_write_bytes = recurrent_state_bytes(cfg, mutable_only=True)
            self._weight_bytes = weight_stream_bytes(cfg)
        # prefix sharing (repro.serving.prefix): the index holds refcounted
        # page references on THIS pool's allocator; ``prefix_acquire`` hands
        # shared table entries to admitted requests, and ``prefix_stats``
        # meters what the reuse avoided (side-channel, never added to totals)
        self.prefix_sharing = prefix_sharing
        self._prefix: Optional[PrefixIndex] = None
        self.prefix_stats = PrefixStats()
        self._pending_hits: Dict[int, PrefixHit] = {}
        if prefix_sharing:
            if not paged:
                raise ValueError("prefix_sharing requires paged=True")
            bad = sorted(set(k for k in cfg.block_kinds_flat()
                             if k not in SHAREABLE_KINDS))
            if bad:
                raise ValueError(
                    f"prefix_sharing supports attention-family blocks only "
                    f"({'/'.join(SHAREABLE_KINDS)}); config has {bad}"
                )
            self._prefix = PrefixIndex(self.allocator)
        self._host_lengths = np.zeros(max_batch, np.int64)
        self._admit_seq = np.zeros(max_batch, np.int64)
        self._admit_counter = 0
        # per-slot sampling temperature (0 = greedy), set at placement so a
        # mixed batch decodes each slot at its own Request.temperature
        self._slot_temp = np.zeros(max_batch, np.float32)
        # host mirror of the current-token vector: placements write HERE
        # (pure numpy) and ``_decode_begin`` ships the mirrors to the device
        # once per step — instead of one eager ``.at[slot].set`` dispatch
        # per placement on both ``lengths`` and ``cur_token``
        self._host_cur_token = np.zeros(max_batch, np.int32)
        # jitted-call dispatch counter (prefill + decode + scatter launched
        # BY this pool; the event engine's fused dispatches count on the
        # engine side) — the per-request-cost observable EngineStats reports
        self.jit_dispatches = 0

        # jitted callables are shared across pools per (cfg, shape) — see
        # the module-level cache above
        self._prefill_impl = prefill_impl_for(cfg, max_seq_len)
        self._decode_impl = decode_impl_for(cfg)
        self._decode_paged_impl = decode_paged_impl_for(cfg)
        self.platform = device.platform if device is not None else jax.default_backend()
        self._jit_prefill = _cached(
            ("prefill_jit", cfg, max_seq_len),
            lambda: jax.jit(self._prefill_impl, static_argnames=("bucket",)))
        self._jit_decode = decode_jit_for(cfg)
        self._jit_decode_paged = decode_jit_for(cfg, paged=True)
        self._jit_carry_tokens = _cached(
            ("carry_tokens_jit",), lambda: jax.jit(_carry_tokens_impl))
        self._jit_scatter = _cached(
            ("scatter_jit",), lambda: jax.jit(_scatter_impl, donate_argnums=(0,)))
        if paged:
            self._jit_scatter_paged = _cached(
                ("scatter_paged_jit", cfg, self.block_tables.shape[1],
                 kv_block_size),
                lambda: jax.jit(self._make_scatter_paged_impl(),
                                donate_argnums=(0,)))

    # ------------------------------------------------------------- internals
    def _make_scatter_paged_impl(self):
        """Copy-on-migrate: blocked rows of the batch-1 prefill cache go to
        the pages ``page_map`` names (unused logical blocks map to the null
        page, which absorbs the garbage rows); slot-layout state leaves
        scatter into the slot row like the dense path."""
        nb = self.block_tables.shape[1]
        bs = self.kv_block_size
        layout = self._layout

        def scatter_paged_impl(big_cache, small_cache, page_map, slot):
            def scat(big, small, is_paged):
                if is_paged:
                    rows = small[:, 0]                              # (n_units, L_max, ...)
                    blocks = rows.reshape(rows.shape[0], nb, bs, *rows.shape[2:])
                    return big.at[:, page_map].set(blocks.astype(big.dtype))
                return jax.lax.dynamic_update_slice_in_dim(big, small, slot, axis=1)

            return jax.tree.map(scat, big_cache, small_cache, layout)

        return scatter_paged_impl

    def _make_prefill_shared_impl(self):
        """Suffix-only prefill over a shared prefix: gather the hit's pages
        out of THIS pool's paged cache into a dense batch-1 row (null-page
        padding absorbs the unused entries; garbage rows sit above
        ``prefix_len`` where the causal mask never looks), then run
        ``prefill_suffix`` for just the un-shared tokens."""
        nb = self.block_tables.shape[1]
        bs = self.kv_block_size
        cfg = self.cfg
        max_seq_len = self.max_seq_len
        layout = self._layout

        def prefill_shared_impl(params, pages, page_map, toks, prefix_len,
                                true_len):
            cache1 = init_cache(cfg, 1, max_seq_len)

            def fill(c1, pg, is_paged):
                if not is_paged:
                    return c1
                rows = pg[:, page_map]              # (n_units, nb, bs, ...)
                rows = rows.reshape(rows.shape[0], nb * bs, *rows.shape[3:])
                return c1.at[:, 0].set(rows.astype(c1.dtype))

            cache1 = jax.tree.map(fill, cache1, pages, layout)
            logits, cache1, _ = prefill_suffix(
                params, cfg, toks, cache1,
                prefix_len=prefix_len, suffix_lengths=true_len,
            )
            return logits, cache1

        return prefill_shared_impl

    def _make_copy_page_impl(self):
        """The COW split's physical copy: duplicate one page across every
        paged cache leaf (``dst`` must be freshly allocated, so no live
        table can alias it)."""
        layout = self._layout

        def copy_page_impl(cache, src, dst):
            def cp(leaf, is_paged):
                return leaf.at[:, dst].set(leaf[:, src]) if is_paged else leaf

            return jax.tree.map(cp, cache, layout)

        return copy_page_impl

    @staticmethod
    @jax.named_scope("sample")
    def _sample(logits, key, temperature):
        """Per-slot sampling: ``temperature`` is a (B,) vector; slots at 0
        take the argmax (bit-identical to the all-greedy seed path), the
        rest draw Gumbel-max at their own temperature. The all-greedy batch
        — the common case — skips the (B, vocab) uniform draw at runtime."""
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def sampled(_):
            t = jnp.maximum(temperature, 1e-6)[:, None]
            gumbel = -jnp.log(
                -jnp.log(jax.random.uniform(key, logits.shape) + 1e-9) + 1e-9)
            s = jnp.argmax(logits / t + gumbel, axis=-1).astype(jnp.int32)
            return jnp.where(temperature > 0.0, s, greedy)

        return jax.lax.cond(
            jnp.any(temperature > 0.0), sampled, lambda _: greedy, None)

    # ------------------------------------------------------ params identity
    def set_params(self, params: Any) -> None:
        """Swap this pool's weights and refresh ``params_token`` so fused
        grouping immediately reflects the new identity."""
        self.params = params_on_device(params, self.device)
        self.params_token = params_token_for(params)

    # ----------------------------------------------------- bank-view cache
    def cache_is_view(self) -> bool:
        return isinstance(self.cache, BankRow)

    def materialize_cache(self) -> None:
        """Replace a ``BankRow`` view with this pool's own dense cache row
        (one jitted gather). No-op when the cache is already concrete."""
        if not isinstance(self.cache, BankRow):
            return
        row = self.cache
        fn = _cached(("bank_row_jit",),
                     lambda: jax.jit(_bank_row_impl))
        if self.device is None:
            self.cache = fn(row.bank.tree, np.int32(row.index))
        else:
            # gather on this pool's device, from the shard holding its row
            self.cache = jax.tree.map(
                lambda x: fn(*row_on_device(x, row.index, self.device)),
                row.bank.tree)
        self.jit_dispatches += 1

    def _bank_write(self, impl_key: Tuple, impl: Callable, rows: Any,
                    slots: Any) -> None:
        """Scatter prefilled ``rows`` into ``slots`` of this pool's bank row
        with the donating ``impl(tree, rows, row, slots)``. Unbound pools
        scatter into the whole stacked tree; a bound pool scatters into the
        shard that holds its row, on its own device, and the bank is
        reassembled around the new shard — no row leaves its device."""
        view = self.cache
        fn = _cached(impl_key, lambda: jax.jit(impl, donate_argnums=(0,)))
        if self.device is None:
            view.bank.tree = fn(view.bank.tree, rows, np.int32(view.index), slots)
            return
        leaves, treedef = jax.tree.flatten(view.bank.tree)
        local = [row_on_device(x, view.index, self.device) for x in leaves]
        others = [[s.data for s in x.addressable_shards if s.device != self.device]
                  for x in leaves]
        new = jax.tree.leaves(fn(treedef.unflatten([a for a, _ in local]),
                                 rows, np.int32(local[0][1]), slots))
        view.bank.tree = treedef.unflatten([
            jax.make_array_from_single_device_arrays(x.shape, x.sharding, o + [n])
            if o else n
            for x, n, o in zip(leaves, new, others)])

    # ------------------------------------------------------- energy plumbing
    def set_operating_point(self, op: OperatingPoint, prefill_op: Optional[OperatingPoint] = None):
        """Apply a controller-resolved point; ``prefill_op`` prices prefill
        tokens separately when one pool runs both phases (colocated engine)."""
        self.op = op
        self.prefill_op = prefill_op if prefill_op is not None else op
        self.stats.note_operating_point(op)
        self._refresh_gauge()

    def _refresh_gauge(self):
        # inside a prefill call the device burns prefill power; between
        # ticks a pool holding live slots burns its decode-point power;
        # an empty pool sits at the idle floor
        if self._in_phase_call and self.prefill_op is not None:
            watts = self.prefill_op.power_w
        elif self.op is not None and self.occupancy() > 0:
            watts = self.op.power_w
        else:
            watts = self.idle_power_w
        if (self.sampler.synchronous and self._metering_active
                and watts != self.gauge()):
            # bracket the step change so the trapezoid integrates the
            # piecewise-constant power signal exactly: close the old level
            # at (now, w_old), open the new one at (now, w_new)
            self.sampler.sample_once()
            self.gauge.set(watts)
            self.sampler.sample_once()
        else:
            self.gauge.set(watts)

    def set_idle_power(self, watts: float):
        """Set the no-work power floor this pool idles at (0 for a
        powered-down fleet replica, the chip's p_idle otherwise) and refresh
        the gauge — bracketed with samples under synchronous metering so the
        step change integrates exactly."""
        self.idle_power_w = float(watts)
        self._refresh_gauge()

    def sample_now(self):
        """Synchronous-metering hook: record a sample at the current clock
        (callers advance the shared VirtualClock, then sample each pool)."""
        if self.sampler.synchronous and self._metering_active:
            self.sampler.advance()

    def advance_time(self, dt_s: float):
        """Advance this pool's (virtual) clock by a modelled duration and
        take a synchronous power sample, so energy integrates over virtual
        time without threads. No-op on a wall clock."""
        if not self.virtual or dt_s <= 0:
            return
        self.clock.advance(dt_s)
        self.sample_now()

    @property
    def current_power_w(self) -> float:
        return self.gauge()

    def _mj_per_token(self, phase: str = "decode") -> float:
        op = self.prefill_op if phase == "prefill" else self.op
        return op.energy_per_token_mj if op is not None else 0.0

    def start_metering(self):
        if self._metering_active:
            return
        self._metering_active = True
        self.sampler.start()                 # resets the trace for this window

    def stop_metering(self) -> float:
        """Stop the sampler; bank the window's joules; return the total."""
        if self._metering_active:
            self._metering_active = False
            self.sampler.stop()
            self._measured_j_total += self.sampler.trace.integrate_trapezoid()
        return self._measured_j_total

    def measured_energy_j(self) -> float:
        """Joules across ALL metering windows (plus the live one, if any) —
        the same lifetime scope as this pool's PhaseStats."""
        live = self.sampler.trace.integrate_trapezoid() if self._metering_active else 0.0
        return self._measured_j_total + live

    # ------------------------------------------------------------- occupancy
    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def has_free_slot(self) -> bool:
        return any(r is None for r in self.slot_req)

    def can_admit(self, req: Request) -> bool:
        """Admission test: a slot AND (paged) blocks for prompt + first
        token. Growth past that is served by alloc-or-preempt, so this is
        the continuous-batching gate: admit whenever blocks are free.

        A prefix-sharing pool admits on *private* need — shared table
        entries cost nothing — and may count index-only pages it could
        evict; the count excludes the hit's own pages, which acquisition
        pins (refcount 2) and so makes unreclaimable."""
        if not self.has_free_slot():
            return False
        if not self.paged:
            return True
        need = self.allocator.blocks_for_tokens(len(req.prompt) + 1)
        if self._prefix is not None:
            entries, _ = self._peek_fitted(req.prompt)
            avail = self.allocator.free_blocks + max(
                self._prefix.reclaimable_blocks() - entries, 0)
            return max(need - entries, 0) <= avail
        return self.allocator.can_alloc(need)

    def occupancy(self) -> int:
        return sum(r is not None for r in self.slot_req)

    def mean_context(self) -> float:
        mask = self.active_mask()
        if not mask.any():
            return 0.0
        # the host mirror tracks the device lengths exactly — no transfer
        return float(self._host_lengths[mask].mean())

    def _ensure_decode_state(self):
        if self.cache is None:
            if self.paged:
                make = lambda: init_paged_cache(
                    self.cfg, self.max_batch,
                    self.allocator.num_blocks + 1,   # + the null page
                    self.kv_block_size,
                )
            else:
                make = lambda: init_cache(self.cfg, self.max_batch, self.max_seq_len)
            # a bound pool's cache is born on its device, never copied there
            self.cache = make() if self.device is None else jax.jit(
                make, out_shardings=jax.sharding.SingleDeviceSharding(self.device))()
            self.lengths = jnp.zeros((self.max_batch,), jnp.int32)
            self.cur_token = jnp.zeros((self.max_batch,), jnp.int32)
            if self.device is not None:
                self.cur_token = jax.device_put(self.cur_token, self.device)

    def active_mask(self) -> np.ndarray:
        return np.array([r is not None for r in self.slot_req])

    def validate(self, req: Request):
        l = len(req.prompt)
        if l + req.max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"request {req.uid}: prompt {l} + max_new {req.max_new_tokens} "
                f"exceeds engine max_seq_len {self.max_seq_len}"
            )
        if self.paged:
            need = self.allocator.blocks_for_tokens(l + req.max_new_tokens)
            if need > self.allocator.num_blocks:
                raise ValueError(
                    f"request {req.uid}: needs {need} cache blocks, pool has "
                    f"{self.allocator.num_blocks} — unservable even alone"
                )

    # ------------------------------------------------------- paged plumbing
    def _slot_blocks(self, slot: int) -> List[int]:
        row = self.block_tables[slot]
        return [int(b) for b in row[row != NULL_PAGE]]

    def _evict(self, slot: int):
        """Preempt-by-eviction (recompute style): free the slot's blocks,
        reset the request, park it on ``self.evicted`` for the scheduler to
        requeue. Greedy decoding makes the recompute token-identical."""
        req = self.slot_req[slot]
        self.allocator.free(self._slot_blocks(slot), owner=req.uid)
        self.block_tables[slot] = NULL_PAGE
        self.slot_req[slot] = None
        self._host_lengths[slot] = 0
        self._slot_temp[slot] = 0.0
        req.output = []
        req.ledger.reset_service()   # TTFT will span the recompute, too
        req.preemptions += 1
        self.evicted.append(req)
        self._refresh_gauge()

    def take_evicted(self) -> List[Request]:
        out, self.evicted = self.evicted, []
        return out

    def _youngest_active_slot(self) -> Optional[int]:
        live = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not live:
            return None
        return max(live, key=lambda i: self._admit_seq[i])

    def _grow_tables(self):
        """Allocate the next block for every slot whose write position has
        crossed a block boundary; preempt the youngest slot on exhaustion.
        Oldest-admitted slots grow first, so under contention the pool
        drains FIFO-ish instead of livelocking."""
        order = sorted(
            (i for i, r in enumerate(self.slot_req) if r is not None),
            key=lambda i: self._admit_seq[i],
        )
        bs = self.kv_block_size
        for slot in order:
            if self.slot_req[slot] is None:      # evicted by an older slot
                continue
            ln = int(self._host_lengths[slot])
            if ln % bs != 0:
                continue
            want = ln // bs
            if want < len(self._slot_blocks(slot)):
                continue
            while True:
                blk = self.allocator.alloc_one(owner=self.slot_req[slot].uid)
                if blk is not None:
                    self.block_tables[slot, want] = blk
                    break
                if self._evict_index_one():
                    continue                      # index page reclaimed; retry
                victim = self._youngest_active_slot()
                self._evict(victim)
                if victim == slot:
                    break                         # evicted ourselves; requeued

    # ------------------------------------------------------- prefix sharing
    def _evict_index_one(self) -> bool:
        """Reclaim one index-only page (allocator pressure relief: tried
        before preempting a live slot). False when sharing is off or the
        index holds nothing reclaimable."""
        if self._prefix is None or not self._prefix.evict_one():
            return False
        self.prefix_stats.evictions += 1
        self.prefix_stats.index_blocks = self._prefix.held_blocks
        return True

    def _alloc_blocks(self, n: int, owner: int) -> List[int]:
        """``allocator.alloc`` with index eviction under pressure — the
        placement-time twin of ``can_admit``'s reclaimable accounting."""
        while not self.allocator.can_alloc(n) and self._evict_index_one():
            pass
        return self.allocator.alloc(n, owner)

    def _fit_hit(self, hit: Optional[PrefixHit],
                 prompt_len: int) -> Optional[PrefixHit]:
        """Cap a hit so the suffix bucket still fits the cache row:
        ``prefix_len + bucket(suffix) <= max_seq_len`` keeps the suffix
        write un-clamped. Demotes to fewer whole shared blocks (never a
        partial boundary) or to a miss."""
        if hit is None:
            return None
        L = prompt_len

        def ok(pt: int) -> bool:
            return pt + min(_bucket(L - pt), self.max_seq_len) <= self.max_seq_len

        if ok(hit.prefix_tokens):
            return hit
        bs = self.kv_block_size
        n = min(len(hit.full_blocks), (L - 1) // bs)
        while n > 0 and not ok(n * bs):
            n -= 1
        if n == 0:
            return None
        return PrefixHit(hit.full_blocks[:n], None, n * bs, n * bs)

    def _peek_fitted(self, prompt) -> Tuple[int, int]:
        """Non-mutating (shared_entries, prefix_tokens) the prompt would
        get after the bucket-fit cap — for admission gates, scheduler token
        budgets and the prefix router."""
        if self._prefix is None:
            return 0, 0
        entries, pt = self._prefix.peek(prompt)
        if entries == 0:
            return 0, 0
        L = len(prompt)

        def ok(p: int) -> bool:
            return p + min(_bucket(L - p), self.max_seq_len) <= self.max_seq_len

        if ok(pt):
            return entries, pt
        bs = self.kv_block_size
        n = min(entries, (L - 1) // bs)
        while n > 0 and not ok(n * bs):
            n -= 1
        return (n, n * bs) if n else (0, 0)

    def prefix_acquire(self, req: Request) -> Optional[PrefixHit]:
        """Look the prompt up in the prefix index and pin the hit: one
        allocator reference per shared table entry, owned by ``req.uid`` —
        the same references the block table will carry, so eviction and
        finish free them through the normal table path. Returns None when
        sharing is off or nothing matched. Call only on the admission path;
        every acquired hit MUST flow into ``place(..., shared=hit)``."""
        if self._prefix is None:
            return None
        self.prefix_stats.lookups += 1
        hit = self._fit_hit(self._prefix.match(req.prompt), len(req.prompt))
        if hit is None:
            self.prefix_stats.misses += 1
            return None
        for b in hit.table_blocks:
            self.allocator.retain(b, req.uid)
        self._pending_hits[req.uid] = hit
        self.prefix_stats.hits += 1
        self.prefix_stats.shared_blocks += hit.shared_entries
        self.prefix_stats.shared_tokens += hit.prefix_tokens
        return hit

    def prefill_cost_tokens(self, req: Request) -> int:
        """Prompt tokens prefill will actually compute for ``req`` — the
        scheduler's token-budget charge (suffix only under a prefix hit;
        at least one token is always recomputed)."""
        if self._prefix is None:
            return len(req.prompt)
        _, pt = self._peek_fitted(req.prompt)
        return max(len(req.prompt) - pt, 1)

    def suffix_tokens(self, req: Request,
                      prefix_tokens: int) -> Tuple[np.ndarray, np.ndarray, int]:
        """The (tokens, true_len, bucket) triple for a suffix-only prefill:
        the un-shared tail of the prompt, padded to its own bucket."""
        sl = len(req.prompt) - prefix_tokens
        bucket = min(_bucket(sl), self.max_seq_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :sl] = req.prompt[prefix_tokens:]
        return toks, np.asarray([sl], np.int32), bucket

    def shared_prefill(self, params, toks, true_len, hit: PrefixHit):
        """Donor-side dispatch of the gather+suffix-prefill program over
        THIS pool's paged cache. Returns (logits, dense cache row) shaped
        exactly like the plain prefill's, so placement is uniform."""
        gather = hit.gather_blocks(self.kv_block_size)
        page_map = np.full(self.block_tables.shape[1], NULL_PAGE, np.int32)
        page_map[:len(gather)] = gather
        fn = _cached(
            ("prefill_shared_jit", self.cfg, self.max_seq_len,
             self.block_tables.shape[1], self.kv_block_size),
            lambda: jax.jit(self._make_prefill_shared_impl()))
        prefix_len = np.asarray([hit.prefix_tokens], np.int32)
        return fn(params, self.cache, page_map, toks, prefix_len, true_len)

    def _register_finished(self, req: Request, slot: int):
        """Donate a finished request's cached transcript to the prefix
        index (prompt + all generated tokens whose KV was written). Runs
        BEFORE the request's blocks are freed, so the pages the index newly
        retains survive the free."""
        cached_len = int(self._host_lengths[slot])
        if cached_len < self.kv_block_size:
            return
        toks = np.concatenate([
            np.asarray(req.prompt, np.int64),
            np.asarray(req.output[:-1], np.int64),
        ])[:cached_len]
        self._prefix.register(toks, self._slot_blocks(slot), cached_len)
        self.prefix_stats.registrations += 1
        self.prefix_stats.index_blocks = self._prefix.held_blocks

    def _cow_guard(self):
        """Copy-on-write: before a decode step, any live slot whose write
        target page is shared (refcount > 1) gets a private copy — alloc a
        fresh page (evicting index entries, then preempting the youngest
        slot, under pressure), duplicate the page in one jitted copy, swap
        the table entry, drop the shared reference. Shared pages are
        thereby never written."""
        bs = self.kv_block_size
        block_bytes = bs * self._kv_token_bytes
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            entry = int(self._host_lengths[slot]) // bs
            blk = int(self.block_tables[slot, entry])
            if blk == NULL_PAGE or not self.allocator.is_shared(blk):
                continue
            while True:
                fresh = self.allocator.alloc_one(owner=req.uid)
                if fresh is not None:
                    break
                if self._evict_index_one():
                    continue
                victim = self._youngest_active_slot()
                self._evict(victim)
                if victim == slot:
                    break
            if self.slot_req[slot] is None:       # preempted ourselves
                continue
            copy_fn = _cached(
                ("copy_page_jit", self.cfg, self.kv_block_size),
                lambda: jax.jit(self._make_copy_page_impl(),
                                donate_argnums=(0,)))
            self.cache = copy_fn(self.cache, blk, fresh)
            self.jit_dispatches += 1
            self.block_tables[slot, entry] = fresh
            self.allocator.release(blk, owner=req.uid)
            self.prefix_stats.cow_splits += 1
            # the split physically moves one block through HBM
            self.traffic.count_reads(1, block_bytes)
            self.traffic.count_writes(1, block_bytes)

    # ------------------------------------------------------------ phase work
    def prefill_tokens(self, req: Request) -> Tuple[np.ndarray, np.ndarray, int]:
        """The (tokens, true_len, bucket) argument triple ``_jit_prefill``
        takes for ``req`` — split out so the event engine's fused admission
        path builds the SAME padded inputs the serial path would."""
        l = len(req.prompt)
        bucket = min(_bucket(l), self.max_seq_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :l] = req.prompt
        return toks, np.asarray([l], np.int32), bucket

    def prefill_request(self, req: Request, *,
                        precomputed: Optional[Tuple[Any, Any]] = None,
                        shared: Optional[PrefixHit] = None,
                        donor: Optional["Pool"] = None) -> Tuple[int, Any]:
        """Run the bucketed batch-1 prefill; returns (first_token, cache row).

        The returned cache row is placed with ``place`` — on this pool for the
        single-pool engine, on the decode pool for the disaggregated cluster.

        ``precomputed`` is the fused-admission handoff: a (logits, cache row)
        pair an engine already computed in a batched dispatch. ONLY the jit
        call is skipped — clock advance, gauge bracketing, ledger stamps,
        RNG-split order and energy accounting run exactly as the serial
        path, so fused admission stays byte-identical per request.

        ``shared`` (a hit from ``donor.prefix_acquire``, donor defaulting to
        this pool) switches to suffix-only prefill: compute, time, and
        joules scale to the un-shared tokens, and the avoided prefill is
        banked in the donor's ``prefix_stats.saved_*`` side-channel — never
        added to any energy total, so conservation is untouched.
        """
        with span("prefill", req.uid):
            l = len(req.prompt)
            work = l if shared is None else l - shared.prefix_tokens
            self._in_phase_call = True
            self._refresh_gauge()
            t0 = self.clock()
            req.ledger.mark_admitted(t0)
            try:
                if precomputed is None:
                    if shared is not None:
                        dp = donor if donor is not None else self
                        toks, true_len, _ = self.suffix_tokens(
                            req, shared.prefix_tokens)
                        logits, cache1 = dp.shared_prefill(
                            self.params, toks, true_len, shared)
                    else:
                        toks, true_len, bucket = self.prefill_tokens(req)
                        logits, cache1 = self._jit_prefill(
                            self.params, toks, true_len, bucket=bucket
                        )
                    self.jit_dispatches += 1
                else:
                    logits, cache1 = precomputed
                with span("prefill.sync"):
                    row = np.asarray(logits)[0]
                    if req.temperature > 0.0:
                        self._key, sub = jax.random.split(self._key)
                        u = np.asarray(jax.random.uniform(sub, row.shape))
                        gumbel = -np.log(-np.log(u + 1e-9) + 1e-9)
                        first = int(np.argmax(row / req.temperature + gumbel))
                    else:
                        first = int(np.argmax(row))
                jax.block_until_ready(logits)
                if self.virtual and self.prefill_op is not None:
                    # modelled prefill duration: the operating point's profile
                    # is per prefill_seq tokens — scale to the tokens actually
                    # computed (the suffix only, under a prefix hit)
                    prof = self.prefill_op.profile
                    self.advance_time(prof.t_total * work / max(prof.tokens, 1))
            finally:
                dt = self.clock() - t0
                self._in_phase_call = False
                self._refresh_gauge()
            mj = self._mj_per_token("prefill")
            joules = mj * work / 1e3
            self.stats.merge_prefill(work, dt, joules)
            req.prefill_s += dt
            req.prefill_j += joules
            if shared is not None:
                dp = donor if donor is not None else self
                saved_j = mj * shared.prefix_tokens / 1e3
                req.prefix_tokens = shared.prefix_tokens
                req.saved_prefill_j += saved_j
                dp.prefix_stats.saved_prefill_tokens += shared.prefix_tokens
                dp.prefix_stats.saved_prefill_j += saved_j
            return first, cache1

    def _place_bookkeeping(self, req: Request, first_token: int, length: int,
                           first_token_s: Optional[float]) -> int:
        """Everything ``place`` does EXCEPT the cache scatter: slot choice,
        host-mirror writes (``lengths``/``cur_token`` reach the device once
        per decode step via ``_decode_begin``, not per placement), stamps,
        gauge. Shared by ``place`` and the multi-row ``place_many``."""
        free = self.free_slots()
        if not free:
            raise RuntimeError("place() on a full pool — check can_admit() first")
        self._ensure_decode_state()
        slot = free[0]
        self._host_lengths[slot] = length
        self._host_cur_token[slot] = first_token
        self._admit_counter += 1
        self._admit_seq[slot] = self._admit_counter
        self._slot_temp[slot] = req.temperature
        req.output.append(first_token)
        req.ledger.mark_first_token(
            self.clock() if first_token_s is None else first_token_s)
        self.slot_req[slot] = req
        self.peak_occupancy = max(self.peak_occupancy, self.occupancy())
        self._refresh_gauge()
        return slot

    def place(self, req: Request, cache1: Any, first_token: int, length: int,
              *, first_token_s: Optional[float] = None,
              shared: Optional[PrefixHit] = None) -> int:
        """Scatter a filled batch-1 cache row into a free slot (migration).

        Paged pools allocate the request's block table first and scatter by
        page (copy-on-migrate); the handoff the decode step sees is purely
        the table row. ``first_token_s`` overrides the first-token stamp:
        with per-pool clocks the prefill timeline produced the token at its
        own (earlier) time, and the event engine may place the row after
        the decode timeline has moved past it.

        With ``shared`` (the hit ``prefix_acquire`` pinned for this
        request), the leading table entries are the hit's pages — already
        referenced by ``req.uid``, so nothing is allocated or copied for
        them: the scatter is masked to the null page there, and the bytes
        the migration avoided are banked in ``prefix_stats``."""
        with span("place", req.uid):
            slot = self._place_bookkeeping(req, first_token, length, first_token_s)
            if self.paged:
                if shared is None and self._prefix is not None:
                    # batched placement paths (place_many) don't thread the
                    # hit — re-find the one prefix_acquire pinned for this uid
                    shared = self._pending_hits.get(req.uid)
                need = self.allocator.blocks_for_tokens(length + 1)
                se = shared.shared_entries if shared is not None else 0
                blocks = self._alloc_blocks(need - se, owner=req.uid)
                page_map = np.full(self.block_tables.shape[1], NULL_PAGE, np.int32)
                if se:
                    page_map[:se] = shared.table_blocks
                page_map[se:need] = blocks
                self.block_tables[slot] = page_map
                scatter_map = page_map.copy()
                if se:
                    scatter_map[:se] = NULL_PAGE      # shared pages: never written
                    self._pending_hits.pop(req.uid, None)
                self.cache = self._jit_scatter_paged(
                    self.cache, cache1, jnp.asarray(scatter_map), slot
                )
                # copy-on-migrate moves the PRIVATE blocks of KV into the pool;
                # shared entries move nothing (the avoided bytes are metered)
                npriv = need - se
                self.traffic.count_writes(
                    npriv, npriv * self.kv_block_size * self._kv_token_bytes
                    + self._state_write_bytes,
                )
                if se:
                    self.prefix_stats.saved_migrate_bytes += (
                        se * self.kv_block_size * self._kv_token_bytes)
            elif isinstance(self.cache, BankRow):
                # write THROUGH the bank: the stacked tree is donated and
                # replaced, so every other member pool's view follows along
                self._bank_write(("bank_scatter_jit",), _bank_scatter_impl,
                                 cache1, np.int32(slot))
            else:
                self.cache = self._jit_scatter(self.cache, cache1, slot)
            self.jit_dispatches += 1
            return slot

    def place_many(self, items: Sequence[Tuple[Request, Any, int, int,
                                               Optional[float]]]) -> List[int]:
        """Place K prefilled rows with ONE jitted scatter dispatch (dense
        pools). ``items`` are (req, cache1, first_token, length,
        first_token_s) in placement order; per-request bookkeeping, stamps
        and gauge updates run request-by-request exactly like K ``place``
        calls — only the K cache scatters fuse into one chained program
        (byte-identical final cache: distinct slots, order preserved).
        Group sizes pad to powers of two with an idempotent repeat of row 0
        so the trace count stays O(log max_batch). Paged pools fall back to
        sequential ``place`` (block allocation is request-granular)."""
        if self.paged or len(items) == 1:
            return [self.place(req, cache1, first, length,
                               first_token_s=ts)
                    for req, cache1, first, length, ts in items]
        with span("place", tuple(req.uid for req, *_ in items)):
            slots = [self._place_bookkeeping(req, first, length, ts)
                     for req, cache1, first, length, ts in items]
            rows = [cache1 for _, cache1, _, _, _ in items]
            pad_slots = list(slots)
            p = 1 << (len(rows) - 1).bit_length()
            rows.extend([rows[0]] * (p - len(rows)))
            pad_slots.extend([pad_slots[0]] * (p - len(pad_slots)))
            if isinstance(self.cache, BankRow):
                self._bank_write(("bank_scatter_multi_jit", p),
                                 _bank_multi_scatter_impl, tuple(rows),
                                 tuple(pad_slots))
            else:
                fn = _cached(
                    ("scatter_multi_jit", self.cfg, self.max_seq_len, p),
                    lambda: jax.jit(_multi_scatter_impl, donate_argnums=(0,)))
                self.cache = fn(self.cache, tuple(rows), tuple(pad_slots))
            self.jit_dispatches += 1
            return slots

    def _req_eos(self, req: Request) -> int:
        return self.eos_token_id if req.eos_token_id is None else req.eos_token_id

    def _decode_begin(self, *, keep_view: bool = False,
                      after: Optional[dict] = None) -> Optional[dict]:
        """Host-side first half of ``decode_once``: block-table growth,
        active mask, RNG split, and the jitted-call argument tuple. Returns
        ``None`` when no slot is live. ``decode_once`` composes this with
        the jit call and ``_decode_finish``; the split exists so the fleet's
        event engine can run many homogeneous pools' decode updates through
        ONE fused jitted step (each pool still splits its own key, so token
        streams are independent of how steps are grouped).

        A cache held as a ``BankRow`` view is materialised here by default
        so serial and tuple-fused consumers see a concrete pytree in
        ``args``; the batched engine path passes ``keep_view=True`` and
        resolves the view itself (either reusing the bank's stacked tree
        directly or gathering rows inside its own program).

        ``after`` is the step still in flight on an overlapping pool: slots
        it gives their last token sit this step out, and the slots it
        serves take their input token from its device output."""
        with span("decode.prepare"):
            if self.paged and any(r is not None for r in self.slot_req):
                self._grow_tables()
                if self._prefix is not None:
                    self._cow_guard()
            active = self.active_mask()
            if self.overlap:
                active &= ~self._finishing(after)
            if not active.any():
                return None
            if not keep_view:
                self.materialize_cache()
            self._ensure_decode_state()
            self._key, sub = jax.random.split(self._key)
            t0 = self.clock()
            # ship the host mirrors once per step (placements only wrote numpy);
            # jit moves numpy args to the device inside dispatch, so no eager
            # per-array device_put is paid here. Copies because the mirrors
            # mutate between this dispatch and the next placement.
            toks = self._host_cur_token.copy()
            if self.overlap:
                toks = self._jit_carry_tokens(self.cur_token, toks,
                                              self._carried(after))
            lengths = self._host_lengths.astype(np.int32)
            temps = self._slot_temp.copy()
            if self.paged:
                args = (self.params, toks, self.cache, lengths,
                        active, self.block_tables.copy(), sub, temps)
            else:
                args = (self.params, toks, self.cache, lengths,
                        active, sub, temps)
            pre = {"active": active, "t0": t0, "args": args}
            if self.overlap:
                pre["reqs"] = [r if a else None
                               for r, a in zip(self.slot_req, active)]
            return pre

    def _finishing(self, inflight: Optional[dict]) -> np.ndarray:
        """Slots whose request the in-flight step gives its last token."""
        out = np.zeros(self.max_batch, bool)
        for i, req in enumerate(inflight["reqs"] if inflight else ()):
            out[i] = req is not None and len(req.output) + 1 >= req.max_new_tokens
        return out

    def _carried(self, inflight: Optional[dict]) -> np.ndarray:
        """Slots whose next input token the in-flight step computes: it
        serves them and no placement has replaced their request since."""
        out = np.zeros(self.max_batch, bool)
        for i, req in enumerate(inflight["reqs"] if inflight else ()):
            out[i] = req is not None and self.slot_req[i] is req
        return out

    def decode_once(self) -> List[Request]:
        """One jitted decode step over all slots; returns finished requests.

        Paged pools grow/evict block tables first, then account the step's
        traffic block-accurately and derive decode joules from it.
        Overlapping pools return what the step before this one finished."""
        if self.overlap:
            return self._decode_overlapped()
        pre = self._decode_begin()
        if pre is None:
            return []
        jit_fn = self._jit_decode_paged if self.paged else self._jit_decode
        with span("decode.dispatch"):
            next_tok, cache, lengths = jit_fn(*pre["args"])
        self.jit_dispatches += 1
        return self._decode_finish(pre, next_tok, cache, lengths)

    def _decode_overlapped(self) -> List[Request]:
        """Dispatch this step, then account the one still in flight. The
        new step is dispatched on the in-flight step's donated cache and
        device tokens, so nothing waits for the host; the host mirrors
        advance at dispatch. A request that stops on its EOS token is
        served once more by the step already dispatched; that token is
        dropped."""
        prev, self._inflight = self._inflight, None
        # with no slot to run, the call only accounts: no prepare span
        live = self.active_mask() & ~self._finishing(prev)
        pre = self._decode_begin(after=prev) if live.any() else None
        if pre is not None:
            with span("decode.dispatch"):
                next_tok, self.cache, self.lengths = self._jit_decode(*pre["args"])
            self.jit_dispatches += 1
            self.cur_token = next_tok
            self._host_lengths[pre["active"]] += 1
            pre["out"] = next_tok
            self._inflight = pre
        if prev is None:
            return []
        return self._decode_finish(prev, prev["out"], None, None)

    def _decode_finish(self, pre: dict, next_tok, cache, lengths) -> List[Request]:
        """Second half of ``decode_once``: adopt the jitted step's outputs
        (an overlapped step's were adopted at dispatch), advance the
        (virtual) clock by the modelled step duration, and do the per-slot
        token/energy/EOS accounting."""
        with span("decode.sync"):
            next_np = np.asarray(next_tok)
        with span("decode.account"):
            return self._decode_account(pre, next_np, next_tok, cache, lengths)

    def _decode_account(self, pre: dict, next_np: np.ndarray, next_tok, cache,
                        lengths) -> List[Request]:
        """``_decode_finish`` once the step's tokens are on the host. An
        overlapped step (``reqs`` in ``pre``) was dispatched after it: the
        pool already holds the later step's cache, tokens and lengths, and
        only the requests the step served, and have not finished, take its
        tokens."""
        overlapped = "reqs" in pre
        reqs = pre["reqs"] if overlapped else self.slot_req
        t0 = pre["t0"]
        if overlapped:
            # steps in flight overlap: each one's time starts where the
            # last one's accounting ended
            t0 = max(t0, self._settled_s)
        else:
            self.cache = cache
            self.lengths = lengths
        finished: List[Request] = []
        if self.virtual and self.op is not None:
            # the modelled step duration at the live operating point IS the
            # virtual-time cost of this decode step
            self.advance_time(self.op.profile.t_total)
        dt = self.clock() - t0
        n_active = sum(r is not None and not r.done for r in reqs)
        if not overlapped:
            self.cur_token = next_tok
            # keep the host mirror in lock-step with the device vector; copy
            # because placements mutate it in place before the next step
            self._host_cur_token = np.array(next_np, dtype=np.int32)

        # ---- energy + traffic attribution for this step ------------------
        mj = self._mj_per_token()
        per_req_j = {}
        read_total = write_total = 0
        if self.paged:
            bs = self.kv_block_size
            block_bytes = bs * self._kv_token_bytes
            blocks_touched = 0
            power = self.op.power_w if self.op is not None else 0.0
            for i, req in enumerate(self.slot_req):
                if req is None:
                    continue
                nb_i = int(self._host_lengths[i]) // bs + 1   # incl. write block
                read_i = nb_i * block_bytes + self._state_read_bytes \
                    + self._weight_bytes // n_active           # amortised weights
                write_i = self._kv_token_bytes + self._state_write_bytes
                blocks_touched += nb_i
                read_total += read_i
                write_total += write_i
                req.decode_read_bytes += read_i
                req.decode_write_bytes += write_i
                if self.hbm_bw_eff > 0 and self.op is not None:
                    per_req_j[i] = joules_from_hbm_traffic(
                        power, read_i + write_i, self.hbm_bw_eff
                    )
            self.traffic.count_reads(blocks_touched, read_total)
            self.traffic.count_writes(n_active, write_total)
            self.traffic.count_step()
        step_j = sum(per_req_j.values()) if per_req_j else mj * n_active / 1e3
        rows_read = rows_held = 0
        if not self.paged:
            rows_held = self.max_batch * self.max_seq_len
            rows_read = int(decode_rows_read(self.cfg, self.platform, pre["args"][3],
                                             pre["active"], self.max_seq_len).sum())
        self.stats.merge_decode(n_active, dt, step_j, read_total, write_total,
                                rows_read, rows_held)

        now = self.clock()
        self._settled_s = now
        for i, req in enumerate(reqs):
            if req is None or req.done:
                continue
            if not overlapped:
                self._host_lengths[i] += 1
            req.decode_s += dt / max(n_active, 1)
            req.decode_j += per_req_j.get(i, mj / 1e3)
            tok = int(next_np[i])
            req.output.append(tok)
            req.ledger.mark_token(now)
            if tok == self._req_eos(req) or len(req.output) >= req.max_new_tokens:
                req.done = True
                req.ledger.mark_finish(now)
                finished.append(req)
                self.slot_req[i] = None
                self._slot_temp[i] = 0.0
                if self.paged:
                    if self._prefix is not None:
                        # donate the transcript to the index BEFORE freeing:
                        # newly-retained pages survive the request's free
                        self._register_finished(req, i)
                    self.allocator.free(self._slot_blocks(i), owner=req.uid)
                    self.block_tables[i] = NULL_PAGE
                    self._host_lengths[i] = 0
        if finished:
            self._refresh_gauge()
        return finished

    # --------------------------------------------------------------- defrag
    def defrag(self):
        """Compact live blocks to the lowest page ids: remap every slot's
        table and physically move the pages in one jitted gather. Decode
        output is invariant (paging is pure layout)."""
        if not self.paged or self.cache is None:
            return
        mapping = self.allocator.defrag()
        if self._prefix is not None:
            # every held page is live, so it appears in the mapping; each
            # trie entry (and stashed hit) is rewritten exactly once
            self._prefix.remap(mapping)
            for hit in self._pending_hits.values():
                hit.full_blocks = [mapping[b] for b in hit.full_blocks]
                if hit.tail_block is not None:
                    hit.tail_block = mapping[hit.tail_block]
        remap = np.arange(self.allocator.num_blocks + 1)
        for old, new in mapping.items():
            remap[old] = new
        self.block_tables = np.where(
            self.block_tables != NULL_PAGE, remap[self.block_tables], NULL_PAGE
        ).astype(np.int32)
        # perm[new_page] = old_page; untouched ids map identity (their
        # contents are dead anyway once the allocator freed them)
        perm = np.arange(self.allocator.num_blocks + 1)
        for old, new in mapping.items():
            perm[new] = old
        perm_j = jnp.asarray(perm)

        def move(leaf, is_paged):
            return leaf[:, perm_j] if is_paged else leaf

        self.cache = jax.tree.map(move, self.cache, self._layout)
