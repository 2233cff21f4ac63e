"""The program's own spans and named scopes in a traced run's trace.

The program marks its serving path with host spans named ``repro.<name>``
(``repro.serving.spans``, on the profiler's clock while its recorder is
on) and its step programs with ``jax.named_scope`` (``PROGRAM_SCOPES``).
``tracing.load`` keeps neither. Here they join its data: the ``repro.*``
host events under ``program_spans`` (``host_spans``) and, per device, the
scope path of each op it can name under ``op_scopes`` ({program: {op:
path}}; ``add_op_scopes``). The path is the ``op_name`` metadata of the instruction of that
name in the compiled program's HLO: a TPU op event keeps its ``tf_op`` in
the event's metadata, which ``ProfileData`` does not expose, and not in
``ev.stats``.

The readings work on a ``tracing.Reduced`` of such data and read nothing
where it lacks the keys they need; ``step_idle_ms`` needs none.
``chipbench/program_parts.py`` runs a cell's traced run with the recorder
on and prints them.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Tuple

from chipbench import tracing

PROGRAM_PREFIX = "repro."
# the named scopes the program puts in its step programs
PROGRAM_SCOPES = ("attn", "kv_write", "mlp", "lm_head", "sample")
DECODE = r"\bjit_decode_impl\b|^decode_impl"
# an HLO instruction with an op_name in its metadata
HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bop_name="([^"]*)"', re.M)
HLO_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)


# --------------------------------------------------------------- loading
def module_name(event_name: str) -> str:
    """A module event's program name: ``jit_decode_impl(2167...)`` ->
    ``jit_decode_impl``."""
    return event_name.split("(")[0]


def hlo_op_names(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction name: op_name metadata}) of one compiled
    program's HLO text."""
    m = HLO_MODULE.search(hlo_text)
    return (m.group(1) if m else "",
            {name: path for name, path in HLO_OP.findall(hlo_text)})


def op_scopes(dev: Dict[str, List],
              hlo: Dict[str, Dict[str, str]]) -> Dict[str, Dict[str, str]]:
    """{program: {op: scope path}} for the ops of one device whose
    program's HLO names them; an op belongs to the program whose module
    event holds its start."""
    modules = sorted(dev["modules"], key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out: Dict[str, Dict[str, str]] = defaultdict(dict)
    for name, start, _ in dev["ops"]:
        j = bisect.bisect_right(starts, start) - 1
        if j < 0 or start > modules[j][1] + modules[j][2]:
            continue
        prog = module_name(modules[j][0])
        path = hlo.get(prog, {}).get(name.lstrip("%"))
        if path:
            out[prog][name] = path
    return dict(out)


def add_op_scopes(data: Dict[str, Any], hlo_texts: Sequence[str]) -> None:
    """Put ``op_scopes`` into each device of ``data`` from the compiled
    HLO of the programs that ran."""
    hlo = dict(hlo_op_names(t) for t in hlo_texts)
    for dev in data["devices"].values():
        dev["op_scopes"] = op_scopes(dev, hlo)


def host_spans(logdir: str) -> List[List]:
    """[[name, start_ns, dur_ns], ...] of the ``repro.*`` host events of
    the trace under ``logdir``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))[-1]
    return [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PROGRAM_PREFIX)]


def trim(data: Dict[str, Any], lo_ns: float, hi_ns: float) -> Dict[str, Any]:
    """``tracing.trim`` that keeps ``program_spans`` and, of ``op_scopes``,
    the ops that are left."""
    events = {d: {k: ev[k] for k in ("ops", "modules")} for d, ev in data["devices"].items()}
    out = tracing.trim({"devices": events, "spans": data["spans"]}, lo_ns, hi_ns)
    for d, ev in data["devices"].items():
        if "op_scopes" in ev:
            names = {o[0] for o in out["devices"][d]["ops"]}
            out["devices"][d]["op_scopes"] = {
                prog: {n: p for n, p in ops.items() if n in names}
                for prog, ops in ev["op_scopes"].items()}
    if "program_spans" in data:
        out["program_spans"] = [s for s in data["program_spans"]
                                if s[1] >= lo_ns and s[1] + s[2] <= hi_ns]
    return out


# ------------------------------------------------------------- reduction
def program(red: tracing.Reduced) -> List[Tuple[float, float, str]]:
    """The program's spans (start_ns, end_ns, name without prefix), in
    start order."""
    return sorted((s[1], s[1] + s[2], s[0][len(PROGRAM_PREFIX):])
                  for s in red.data.get("program_spans", []))


def gaps(red: tracing.Reduced) -> List[Tuple[float, float]]:
    """Idle stretches of the first chip in the window."""
    if not red.chips:
        return []
    busy = red.busy[red.chips[0]]
    edges = [red.lo] + [x for iv in busy for x in iv] + [red.hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]


def idle_by_span(red: tracing.Reduced) -> List[Tuple[float, float, Tuple[str, ...]]]:
    """Idle stretches of the first chip in the window, cut wherever one of
    the program's spans opens or closes: each piece with the names of the
    spans covering it, outermost first, so the last is the innermost
    (empty: unattributed)."""
    idle = gaps(red)
    if not idle:
        return []
    spans = program(red)
    marks = sorted(m for i, (s, e, _) in enumerate(spans)
                   if e > idle[0][0] and s < idle[-1][1]
                   for m in ((s, 1, i), (e, 0, i)))        # a close sorts first
    active: Dict[int, Tuple[float, float, str]] = {}

    def apply(mark):
        if mark[1]:
            active[mark[2]] = spans[mark[2]]
        else:
            active.pop(mark[2], None)

    def path():
        return tuple(n for _, _, n in sorted(active.values(), key=lambda x: (x[0], -x[1])))

    out = []
    mi = 0
    for gs, ge in idle:
        while mi < len(marks) and marks[mi][0] <= gs:
            apply(marks[mi])
            mi += 1
        t = gs
        while mi < len(marks) and marks[mi][0] < ge:
            if marks[mi][0] > t:
                out.append((t, marks[mi][0], path()))
                t = marks[mi][0]
            apply(marks[mi])
            mi += 1
        out.append((t, ge, path()))
    return out


def step_idle(red: tracing.Reduced,
              pattern: str = DECODE) -> Tuple[int, Dict[Tuple[str, ...], float]]:
    """Idle nanoseconds of the first chip between the end of each program
    matching ``pattern`` there and the start of the next, by the spans
    covering them (``idle_by_span``); and the number of those intervals."""
    if not red.chips:
        return 0, {}
    rx = re.compile(pattern)
    progs = sorted((s, s + d) for n, s, d in red.data["devices"][red.chips[0]]["modules"]
                   if rx.search(n) and s >= red.lo and s + d <= red.hi)
    between = [(a[1], b[0]) for a, b in zip(progs, progs[1:])]
    starts = [s for s, _ in between]
    out: Dict[Tuple[str, ...], float] = defaultdict(float)
    for s, e, names in idle_by_span(red):
        # a program lies between two intervals, so a piece of idle meets at
        # most the last interval that starts before its end (a piece may
        # start inside the tail of a module event)
        j = bisect.bisect_left(starts, e) - 1
        if j >= 0:
            part = min(e, between[j][1]) - max(s, between[j][0])
            if part > 0:
                out[names] += part
    return len(between), dict(out)


def _self_durations(evs: Sequence[Tuple[float, float, str]]) -> List[List]:
    """[name, self ns] of each op event ``(start, -dur, name)``, given in
    start order: its duration less the ops nested inside it."""
    out: List[List] = []
    stack: List[Tuple[float, int]] = []          # (end, index) of open parents
    for s, neg, n in evs:
        d = -neg
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= d
        out.append([n, d])
        stack.append((s + d, len(out) - 1))
    return out


def scope_times(red: tracing.Reduced,
                pattern: str = DECODE) -> Tuple[int, Dict[str, float]]:
    """Device self seconds of the ops that ran inside the programs matching
    ``pattern``, by each op's scope path (``op_scopes``; "" where it names
    none), summed over the chips that ran; and the number of those program
    calls."""
    rx = re.compile(pattern)
    calls = 0
    out: Dict[str, float] = defaultdict(float)
    for dev in red.chips:
        ev = red.data["devices"][dev]
        scopes = ev.get("op_scopes", {})
        ops = sorted((s, -d, n) for n, s, d in ev["ops"])
        starts = [o[0] for o in ops]
        for name, s, d in ev["modules"]:
            if not (rx.search(name) and s >= red.lo and s + d <= red.hi):
                continue
            calls += 1
            paths = scopes.get(module_name(name), {})
            inside = ops[bisect.bisect_left(starts, s):bisect.bisect_right(starts, s + d)]
            for n, self_ns in _self_durations(inside):
                out[paths.get(n, "")] += self_ns / 1e9
    return calls, dict(out)


def program_scope(path: str) -> Optional[str]:
    """The innermost of the program's own scopes in an op's scope path, or
    None."""
    for part in reversed(path.split("/")):
        if part in PROGRAM_SCOPES:
            return part
    return None


# -------------------------------------------------------------- readings
def _idle_ms(red: tracing.Reduced, under: Optional[set]) -> Optional[float]:
    """ms a decode step of the idle between decode programs, or of its part
    under any of the spans ``under`` (None: all of it). Nothing where the
    programs do not repeat, or ``under`` is asked of a trace without the
    program's spans."""
    if under is not None and not red.data.get("program_spans"):
        return None
    n, idle = step_idle(red)
    if not n:
        return None
    return sum(v for names, v in idle.items()
               if under is None or under & set(names)) / n / 1e6


def _scoped_ms(red: tracing.Reduced, scope: Optional[str]) -> Optional[float]:
    """Device self ms, per decode program call, of its ops under the
    program scope ``scope`` (None: under none of them). Nothing where the
    program names no scope."""
    calls, by_path = scope_times(red)
    scopes = {p: program_scope(p) for p in by_path}
    if not calls or not any(scopes.values()):
        return None
    return 1e3 * sum(v for p, v in by_path.items() if scopes[p] == scope) / calls


# name -> its reading of a trace, in ms; None where the trace cannot say
READINGS = {
    # all device idle between the end of one decode program and the next
    "step_idle_ms": lambda r: _idle_ms(r, None),
    # the part of it under the host work from the step's arguments to its launch
    "decode_prepare_idle_ms": lambda r: _idle_ms(r, {"decode.prepare", "decode.dispatch"}),
    # ... under energy, EOS and ledger stamps after the tokens reach the host
    "decode_account_idle_ms": lambda r: _idle_ms(r, {"decode.account"}),
    # ... under admission (``Scheduler.tick``), prefills and placements inside
    "admit_idle_ms": lambda r: _idle_ms(r, {"admit"}),
    # device time of the cache or state write of every block
    "decode_kv_write_ms": lambda r: _scoped_ms(r, "kv_write"),
    # device time under no program scope: norms, residuals, copies XLA put in
    "decode_unscoped_ms": lambda r: _scoped_ms(r, None),
}


def readings(red: tracing.Reduced) -> Dict[str, float]:
    """Every reading that reads something on ``red``."""
    out = {name: f(red) for name, f in READINGS.items()}
    return {k: v for k, v in out.items() if v is not None}


def idle_parts(red: tracing.Reduced) -> Dict[str, float]:
    """ms a decode step of the idle between decode programs, by the
    innermost program span over it ("" where none is)."""
    n, idle = step_idle(red)
    out: Dict[str, float] = defaultdict(float)
    for names, v in idle.items():
        out[names[-1] if names else ""] += v / n / 1e6
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
