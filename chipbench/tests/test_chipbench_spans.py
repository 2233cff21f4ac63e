"""The readings of the program's own spans and named scopes
(``program_trace``): the idle of the chip cut by the innermost ``repro.*``
span, and the decode program's device time by scope, on a made-up trace
and on a piece of a chip trace recorded on a TPU v5e; ``program_parts``'s
traced run on the CPU. The readers that were there before read what they
read before on the older recorded trace."""
import json
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import chipbench_testkit  # noqa: E402,F401

from chipbench import core, program_trace, tracing  # noqa: E402

DATA = Path(__file__).resolve().parents[1] / "data"
MS = 1e6
NEW = ("step_idle_ms", "decode_prepare_idle_ms", "decode_account_idle_ms",
       "admit_idle_ms", "decode_kv_write_ms", "decode_unscoped_ms")
SCOPES = {
    "jit_decode_impl": {"%fusion.kv": "jit(decode_impl)/while/body/kv_write/select_n",
                        "%while.1": "jit(decode_impl)/while",
                        "%fusion.gone": "jit(decode_impl)/while/body/mlp/dot"},
    # the same op name in another program: a scope of its own
    "jit_prefill_impl": {"%fusion.kv": "jit(prefill_impl)/while/body/attn/dot_general"},
}


def _ms(*spans):
    return [[f"repro.{n}", s * MS, (e - s) * MS] for n, s, e in spans]


def _made_up(with_program=True):
    """Three decode programs (1-4, 8-11, 15-18 ms) and a prefill (5-6 ms);
    each decode a loop of 3 ms holding a 1 ms cache write and a 1 ms copy.
    The first decode's module event outlasts its ops by 0.2 ms. Host spans
    of two steps cover the idle between the decode programs."""
    ops, modules = [], []
    for t in (1, 8, 15):
        ops += [["%while.1", t * MS, 3 * MS], ["%fusion.kv", t * MS, 1 * MS],
                ["%copy.1", (t + 1.5) * MS, 1 * MS]]
        modules.append(["jit_decode_impl(7)", t * MS, (3.2 if t == 1 else 3) * MS])
    ops.append(["%fusion.kv", 5 * MS, 1 * MS])
    modules.append(["jit_prefill_impl(9)", 5 * MS, 1 * MS])
    data = {"devices": {"0": {"ops": ops, "modules": modules}},
            "spans": [["cb.window", 0.0, 20 * MS]]}
    if with_program:
        data["devices"]["0"]["op_scopes"] = SCOPES
        data["program_spans"] = _ms(
            ("step", 0.5, 8.5), ("decode.account", 4, 4.5), ("admit", 4.5, 6.5),
            ("prefill", 4.6, 6.2), ("prefill.sync", 5.5, 6.1),
            ("decode.prepare", 7, 7.5), ("decode.dispatch", 7.5, 8.2),
            ("step", 8.6, 15.5), ("decode.account", 11, 12), ("admit", 12, 12.2),
            ("decode.prepare", 14, 14.6), ("decode.dispatch", 14.6, 15.2))
    return data


def _read(reduced, name):
    """A reading of ``program_trace``; ``step_idle_ms`` through its reader."""
    if name == "step_idle_ms":
        run = types.SimpleNamespace(reduced=reduced, notes={})
        run.note = run.notes.__setitem__
        return core.metric_reader(name).read(run)
    return program_trace.READINGS[name](reduced)


def test_idle_goes_to_the_innermost_span():
    r = tracing.Reduced(_made_up())
    pieces = [(s / MS, e / MS, names) for s, e, names in program_trace.idle_by_span(r)]
    assert pieces[:2] == [(0, 0.5, ()), (0.5, 1, ("step",))]
    assert (4.6, 5, ("step", "admit", "prefill")) in pieces
    assert (6, 6.1, ("step", "admit", "prefill", "prefill.sync")) in pieces
    assert (6.5, 7, ("step",)) in pieces
    assert (12.2, 14, ("step",)) in pieces
    assert pieces[-1] == (18, 20, ())
    # the pieces are the idle, cut and nothing more
    total = sum(e - s for s, e, _ in pieces) * MS
    assert total == pytest.approx(r.window_s * 1e9 - r.busy_s() * 1e9)
    # a piece ends where a span opens or closes
    assert (4, 4.5, ("step", "decode.account")) in pieces
    assert (4.5, 4.6, ("step", "admit")) in pieces


def test_step_idle_and_its_parts_on_a_made_up_trace():
    r = tracing.Reduced(_made_up())
    n, idle = program_trace.step_idle(r)
    assert n == 2
    # between the decodes: 4.2-5 and 6-8 ms, then 11-15 ms; the idle from
    # 4 ms starts inside the first module event and counts from its end
    assert sum(idle.values()) == pytest.approx(6.8 * MS)
    got = {m: _read(r, m) for m in NEW}
    assert got["step_idle_ms"] == pytest.approx(3.4)
    assert got["decode_prepare_idle_ms"] == pytest.approx((1.0 + 1.0) / 2)
    assert got["decode_account_idle_ms"] == pytest.approx((0.3 + 1.0) / 2)
    assert got["admit_idle_ms"] == pytest.approx((1.0 + 0.2) / 2)
    parts = sum(got[m] for m in NEW[1:4])
    assert parts <= got["step_idle_ms"]
    assert got == program_trace.readings(r)
    # by the innermost span: 4.2-4.5 under decode.account, 4.5-4.6 admit,
    # 4.6-5 prefill, 6-6.1 prefill.sync, 6.1-6.2 prefill, 6.2-6.5 admit,
    # 6.5-7 step, 7-7.5 prepare, 7.5-8 dispatch; 11-12 account, 12-12.2
    # admit, 12.2-14 step, 14-14.6 prepare, 14.6-15 dispatch
    assert program_trace.idle_parts(r) == pytest.approx({
        "step": 1.15, "decode.account": 0.65, "admit": 0.3, "prefill": 0.25,
        "prefill.sync": 0.05, "decode.prepare": 0.55, "decode.dispatch": 0.45})


def test_decode_time_by_scope_on_a_made_up_trace():
    r = tracing.Reduced(_made_up())
    calls, by_path = program_trace.scope_times(r)
    assert calls == 3
    # self times: the loop less its body; the prefill's op of the same name
    # is not the decode program's
    assert by_path == pytest.approx({SCOPES["jit_decode_impl"]["%fusion.kv"]: 0.003,
                                     "jit(decode_impl)/while": 0.003, "": 0.003})
    assert _read(r, "decode_kv_write_ms") == pytest.approx(1.0)
    assert _read(r, "decode_unscoped_ms") == pytest.approx(2.0)


def test_without_the_programs_spans_and_scopes_the_new_readers_read_nothing():
    r = tracing.Reduced(_made_up(with_program=False))
    got = {m: _read(r, m) for m in NEW}
    # the idle between decode programs needs no span
    assert got.pop("step_idle_ms") == pytest.approx(3.4)
    assert got == dict.fromkeys(got)


def test_the_new_keys_leave_the_old_reduction_as_it_was():
    old, new = tracing.Reduced(_made_up(False)), tracing.Reduced(_made_up())
    assert new.breakdown() == old.breakdown()
    assert new.idle_gaps() == old.idle_gaps()


def test_trim_keeps_the_new_keys():
    t = program_trace.trim(_made_up(), 0.0, 9.5 * MS)
    assert [s[0] for s in t["program_spans"]] == [
        "repro.step", "repro.decode.account", "repro.admit", "repro.prefill",
        "repro.prefill.sync", "repro.decode.prepare", "repro.decode.dispatch"]
    # scopes of ops the window no longer holds go with them
    kept = dict(SCOPES["jit_decode_impl"])
    del kept["%fusion.gone"]
    assert t["devices"]["0"]["op_scopes"] == {
        "jit_decode_impl": kept, "jit_prefill_impl": SCOPES["jit_prefill_impl"]}
    r = tracing.Reduced(t)
    assert program_trace.step_idle(r)[0] == 0
    # without the new keys it is ``tracing.trim``
    assert program_trace.trim(_made_up(False), 0.0, 9.5 * MS) == tracing.trim(
        _made_up(False), 0.0, 9.5 * MS)


def test_op_scopes_from_a_compiled_programs_hlo():
    import jax
    import jax.numpy as jnp

    def step(x, buf):
        with jax.named_scope("kv_write"):
            buf = jnp.where(jnp.arange(buf.shape[0])[:, None] == 2, x, buf)
        return buf.sum(), buf

    text = jax.jit(step).lower(jnp.ones((1, 8)), jnp.zeros((16, 8))).compile().as_text()
    module, names = program_trace.hlo_op_names(text)
    assert module == "jit_step"
    kv = [n for n, p in names.items() if program_trace.program_scope(p) == "kv_write"]
    assert kv
    # an op event names the instruction with a leading "%"; the module event
    # that holds it names the program
    dev = {"ops": [["%" + kv[0], 10.0, 1.0], ["%" + kv[0], 30.0, 1.0]],
           "modules": [["jit_step(1)", 9.0, 5.0], ["jit_other(2)", 29.0, 5.0]]}
    scopes = program_trace.op_scopes(dev, dict([(module, names)]))
    assert scopes == {"jit_step": {"%" + kv[0]: names[kv[0]]}}


def test_host_spans_reads_the_programs_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.serving import spans

    spans.clear()
    spans.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("cb.window"):
            with spans.span("step"):
                with spans.span("decode.sync"):
                    jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
        spans.disable()
        recs = list(spans.records())
        spans.clear()
    host = program_trace.host_spans(str(tmp_path))
    assert [s[0] for s in host] == ["repro.step", "repro.decode.sync"]
    assert [r[0] for r in recs] == ["step", "decode.sync"]
    # ``tracing.load`` keeps the benchmark's own spans only, as it did
    data = tracing.load(str(tmp_path))
    assert [s[0] for s in data["spans"]] == ["cb.window"]
    data["program_spans"] = host
    r = tracing.Reduced(data)
    assert [n for _, _, n in program_trace.program(r)] == ["step", "decode.sync"]


@pytest.fixture(scope="module")
def recorded_old():
    return tracing.Reduced(json.loads((DATA / "trace_v5e_chat.json").read_text()))


def test_the_old_readers_read_the_old_trace_as_before(recorded_old):
    r = recorded_old
    run = types.SimpleNamespace(reduced=r, spans=None)
    got = {m: core.metric_reader(m).read(run)
           for m in ("decode_host_gap_ms", "decode_step_ms", "device_idle.saturated")}
    # the values the reduction gave before the program had spans of its own
    assert got == {"decode_host_gap_ms": 1.083523375, "decode_step_ms": 32.519481,
                   "device_idle.saturated": 12.548558666666654}
    assert (r.busy_s(), r.window_s) == (0.262354324, 0.3)
    assert r.breakdown() == {
        "device_ops": [["%bitcast_add_fusion.3", 0.039939725],
                       ["%copy_dynamic-update-slice_fusion.4", 0.030602451],
                       ["%copy.103", 0.029435616], ["%copy.104", 0.029434509],
                       ["%fusion.156", 0.019606059],
                       ["%copy_dynamic-update-slice_fusion.5", 0.015923111],
                       ["%broadcast_select_fusion.3", 0.015319456],
                       ["%constant_dynamic-slice_fusion.13", 0.013305168],
                       ["%constant_dynamic-slice_fusion.10", 0.013216722],
                       ["%convert_bitcast_fusion.5", 0.010110126]],
        "idle_gaps": [["decode_once", 0.03516629199999996],
                      ["host_idle", 0.0016071079999999969], ["tick", 0.000872276]]}
    # the old trace has no program spans or scopes: only the idle between
    # decode programs reads, and the new readers leave the rest out
    got = {m: _read(r, m) for m in NEW}
    assert got.pop("step_idle_ms") > 0
    assert got == dict.fromkeys(got)


@pytest.fixture(scope="module")
def recorded():
    """0.3 s of qwen3-4b.chat's traced run on a TPU v5e, with the program's
    spans and the decode program's op scopes (from its compiled HLO)."""
    return tracing.Reduced(json.loads((DATA / "trace_v5e_chat_spans.json").read_text()))


# what each reading gives on the recorded chip trace
RECORDED = {
    "step_idle_ms": 4.863213833333333, "decode_prepare_idle_ms": 1.7702541666666667,
    "decode_account_idle_ms": 0.17355666666666666, "admit_idle_ms": 0.44996383333333334,
    "decode_kv_write_ms": 1.8960352857142855, "decode_unscoped_ms": 18.09871285714278}


@pytest.mark.parametrize("name", NEW)
def test_each_reading_on_a_recorded_chip_trace(recorded, name):
    assert _read(recorded, name) == pytest.approx(RECORDED[name], rel=1e-12)


def test_the_new_readers_on_a_recorded_chip_trace(recorded):
    r = recorded
    got = program_trace.readings(r)
    assert got == pytest.approx(RECORDED, rel=1e-12)
    assert sum(got[m] for m in NEW[1:4]) <= got["step_idle_ms"]
    # the step idle is the device's idle between decode programs, counted
    # straight from the busy union
    progs = sorted((s, s + d) for _, s, d in r.programs(program_trace.DECODE))
    between = sum(min(ge, b[0]) - max(gs, a[1]) for a, b in zip(progs, progs[1:])
                  for gs, ge in program_trace.gaps(r) if ge > a[1] and gs < b[0])
    assert got["step_idle_ms"] == pytest.approx(between / (len(progs) - 1) / 1e6)
    # every op of the decode program is counted once: its scopes' self times
    # add up to the program's device time
    calls, by_path = program_trace.scope_times(r)
    step_ms = core.metric_reader("decode_step_ms").read(types.SimpleNamespace(reduced=r))
    assert 1e3 * sum(by_path.values()) / calls == pytest.approx(step_ms, rel=1e-3)
    assert {program_trace.program_scope(p) for p in by_path} == {
        None, "attn", "kv_write", "mlp", "lm_head", "sample"}


def test_the_old_readers_read_the_spans_trace_alike(recorded):
    # the program's spans are kept apart from the benchmark's ``cb.*`` ones
    assert {n for _, _, n in recorded.host} == {"tick", "decode_once", "prefill_request",
                                                 "place"}
    bd = recorded.breakdown()
    assert [n for n, _ in bd["idle_gaps"]][0] == "decode_once"


def test_program_parts_keeps_the_programs_spans_in_a_traced_run(monkeypatch):
    from chipbench import program_parts
    from chipbench_testkit import reduced_model, small_mix
    from repro.serving import spans

    seen = {}
    add = program_trace.add_op_scopes

    def spy_scopes(data, hlo_texts):
        seen["hlo"] = list(hlo_texts)
        add(data, hlo_texts)

    def spy_readings(red):
        seen["spans"] = {s[0] for s in red.data["program_spans"]}
        return {}

    monkeypatch.setattr(program_trace, "add_op_scopes", spy_scopes)
    monkeypatch.setattr(program_trace, "readings", spy_readings)
    saved = tracing.Profiler
    man = core.manifest()
    cell = "qwen3-4b.decode-heavy"
    c = core.cell(man, cell)
    res = program_parts.run_parts(
        cell, 2**31 + 13, 2.0, require_tpu=False,
        model=reduced_model(c["config"], limit=1.0, man=man),
        mix=small_mix(c["traffic"], man), reduced=True, log=lambda *_: None)
    # the program's spans of the profiled steps are in the trace
    assert {"repro.step", "repro.decode.prepare", "repro.decode.dispatch",
            "repro.decode.sync", "repro.decode.account"} <= seen["spans"]
    # the decode program's HLO names its scopes
    (hlo,) = seen["hlo"]
    module, names = program_trace.hlo_op_names(hlo)
    assert module == "jit_decode_impl"
    assert {"attn", "kv_write", "mlp", "lm_head", "sample"} <= {
        program_trace.program_scope(p) for p in names.values()}
    # the CPU has no TPU planes: nothing reads, and the run's line is whole
    assert res["program_parts"] == {"readings": {}}
    assert set(res["metrics"]) == {"decode_occupancy", "decode_mfu"}
    # the recorder and the harness are left as they were
    assert not spans.enabled() and not spans.records()
    assert tracing.Profiler is saved
