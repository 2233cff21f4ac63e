"""The serving path's span recorder (``repro.serving.spans``): off it costs
one shared null context and records nothing; on, its spans nest by parent
index, a request's prefill and place share its uid, and each decode step
leaves one ``decode.*`` span of each kind on the barrier path and on the
event engine's fused path."""
import time
from collections import Counter

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core import EnergyModel
from repro.core.traces import TracedRequest
from repro.hw import H200_SXM
from repro.models import init_params
from repro.serving import ClockSpec, Fleet, FleetSpec, PoolSpec, ReplicaSpec, spans

ARCH = "gemma-2b"
DECODE = ("decode.prepare", "decode.dispatch", "decode.sync", "decode.account")


@pytest.fixture(scope="module")
def params():
    return {ARCH: init_params(reduced_config(ARCH), jax.random.PRNGKey(0))}


@pytest.fixture
def recorder():
    """The recorder, on and empty; off and empty again afterwards."""
    spans.clear()
    spans.enable()
    yield spans
    spans.disable()
    spans.clear()


def _fleet(params, n=1, clock=None):
    spec = FleetSpec(replicas=tuple(
        ReplicaSpec(name=f"r{i}", arch=ARCH, clock=ClockSpec(mode="lock"),
                    decode=PoolSpec(batch=2), max_seq_len=64,
                    prefill_chunk_tokens=64)
        for i in range(n)), router="jsq")
    return Fleet.from_spec(spec, emodel=EnergyModel(H200_SXM), clock=clock,
                           params_for=params)


def _serve_barrier(fleet, n=3, max_new=4):
    rng = np.random.default_rng(5)
    reqs = [fleet.submit(rng.integers(1, 100, 12 + i).astype(np.int32), max_new)
            for i in range(n)]
    while fleet.busy():
        fleet.step()
    return reqs


def _chain(recs, i):
    """Names from record ``i`` up through its parents."""
    out = []
    while i >= 0:
        out.append(recs[i][0])
        i = recs[i][3]
    return out


def test_off_is_one_null_context_and_records_nothing(params, monkeypatch):
    spans.disable()
    spans.clear()
    assert spans.span("step") is spans.span("decode.sync", uid=3)

    def no_annotation(*a, **k):
        raise AssertionError("a TraceAnnotation was built with the recorder off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", no_annotation)
    reqs = _serve_barrier(_fleet(params, clock=time.perf_counter))
    assert all(r.done for r in reqs)
    assert spans.records() == []


def test_on_spans_nest_and_a_request_keeps_its_uid(params, recorder):
    reqs = _serve_barrier(_fleet(params, clock=time.perf_counter))
    recs = list(recorder.records())
    assert recs and all(r[2] is not None and r[2] >= r[1] for r in recs)
    # a parent opens before its child and closes after it
    for name, t0, t1, parent, _ in recs:
        if parent >= 0:
            p = recs[parent]
            assert p[1] <= t0 and t1 <= p[2]
    for req in reqs:
        mine = [(i, r) for i, r in enumerate(recs) if r[4] == req.uid]
        assert [r[0] for _, r in mine] == ["prefill", "place"]
        for i, _ in mine:
            assert _chain(recs, i)[1:] == ["admit", "step"]
    syncs = [i for i, r in enumerate(recs) if r[0] == "prefill.sync"]
    assert len(syncs) == len(reqs)
    assert all(_chain(recs, i)[1:3] == ["prefill", "admit"] for i in syncs)
    assert {r[0] for r in recs if r[0] == "controller"} == {"controller"}


def test_barrier_steps_leave_one_decode_span_of_each_kind(params, recorder):
    fleet = _fleet(params, clock=time.perf_counter)
    _serve_barrier(fleet)
    recs = list(recorder.records())
    n = Counter(r[0] for r in recs)
    steps = fleet.replicas[0].decode_pool.stats.decode_steps
    assert steps > 0
    assert [n[k] for k in DECODE] == [steps] * 4
    for i, r in enumerate(recs):
        if r[0] in DECODE:
            assert _chain(recs, i)[1:] == ["step"]
    # every step that decoded holds its spans in order: the wall-clock
    # decode pool overlaps, so a step prepares and dispatches its own
    # decode, then syncs and accounts the one before it
    decoded = []
    for i, r in enumerate(recs):
        if r[0] == "step":
            kids = [k[0] for k in recs if k[3] == i and k[0] in DECODE]
            assert kids in ([], list(DECODE[:2]), list(DECODE[2:]), list(DECODE))
            decoded += [kids] if kids else []
    assert decoded[0] == list(DECODE[:2]) and decoded[-1] == list(DECODE[2:])


def test_the_fused_path_leaves_one_decode_span_of_each_kind(params, recorder):
    fleet = _fleet(params, n=4)
    trace = [TracedRequest(arrival_s=0.0,
                           prompt=np.arange(1, 17, dtype=np.int32) + i,
                           max_new_tokens=5) for i in range(8)]
    fleet.run_trace(trace, engine_opts={"fast_path_min": 2})
    st = fleet.last_engine_stats
    assert st.fused_decode_calls > 0
    recs = list(recorder.records())
    n = Counter(r[0] for r in recs)
    # the fused path dispatches per group: no decode.dispatch of its own
    assert n["decode.prepare"] == n["decode.sync"] == n["decode.account"] \
        == st.decode_steps
    assert n["decode.dispatch"] == st.serial_decode_calls
    assert n["decode.fused"] == st.fused_decode_calls
    for i, r in enumerate(recs):
        if r[0] == "decode.fused":
            kids = Counter(k[0] for k in recs if k[3] == i)
            assert kids["decode.sync"] == kids["decode.account"] >= 1
            assert kids["decode.sync"] <= r[4]        # members <= padded size


def test_the_list_is_bounded(recorder, monkeypatch):
    monkeypatch.setattr(spans, "CAP", 3)
    with spans.span("a"):
        for _ in range(4):
            with spans.span("b", uid=1):
                pass
    assert [r[0] for r in spans.records()] == ["a", "b", "b"]
    assert spans.dropped == 2
    assert all(r[2] is not None for r in spans.records())


def test_a_span_names_its_annotation_with_the_prefix(recorder, monkeypatch):
    names = []
    real = jax.profiler.TraceAnnotation

    def seen(name, **kw):
        names.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", seen)
    with spans.span("step"):
        with spans.span("admit"):
            pass
    assert names == ["repro.step", "repro.admit"]
    assert [r[3] for r in spans.records()] == [-1, 0]
