"""Spans around the program's layers, the profiler, and the reduction of
its trace to device time, busy union and idle gaps.

In a traced run ``Spans`` wraps four program methods in
``jax.profiler.TraceAnnotation`` (named ``cb.<method>``) and records each
call's host-clock start and end, with what the call worked on: the cached
lengths of the active slots for a decode step, the prompt length for a
prefill. ``Profiler`` traces a sub-window of the measured window, marked by
a ``cb.window`` span.

``load`` turns an ``.xplane.pb`` into plain data: per TPU device, its op
and module events; per host thread, its ``cb.*`` spans. ``reduce`` works on
that data only, so a recorded trace can be kept as JSON and checked.
"""
from __future__ import annotations

import glob
import re
import time
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "cb."


# ------------------------------------------------------------------ spans
class Spans:
    """Host spans around ``Scheduler.tick``, ``Pool.prefill_request``,
    ``Pool.place`` and ``Pool.decode_once``, on while ``installed``."""

    def __init__(self):
        self.records: List[Tuple[str, float, float, Any]] = []
        self._undo: List[Tuple[Any, str, Any]] = []
        self.recording = False

    def install(self) -> None:
        import jax

        from repro.serving.fleet import Scheduler
        from repro.serving.pool import Pool

        def info_decode(pool, *a, **k):
            return (pool.role, [len(r.prompt) + len(r.output) - 1
                                for r in pool.slot_req if r is not None])

        def info_prefill(pool, req, *a, **k):
            return len(req.prompt)

        targets = [(Scheduler, "tick", None), (Pool, "prefill_request", info_prefill),
                   (Pool, "place", None), (Pool, "decode_once", info_decode)]
        for cls, name, info in targets:
            orig = getattr(cls, name)

            def wrapped(self_, *a, _orig=orig, _name=name, _info=info, **k):
                if not self.recording:
                    return _orig(self_, *a, **k)
                meta = _info(self_, *a, **k) if _info else None
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(SPAN_PREFIX + _name):
                    out = _orig(self_, *a, **k)
                self.records.append((_name, t0, time.perf_counter(), meta))
                return out

            setattr(cls, name, wrapped)
            self._undo.append((cls, name, orig))

    def uninstall(self) -> None:
        for cls, name, orig in reversed(self._undo):
            setattr(cls, name, orig)
        self._undo.clear()

    def of(self, name: str) -> List[Tuple[str, float, float, Any]]:
        return [r for r in self.records if r[0] == name]


class Profiler:
    """Traces ``[start_s, start_s + length_s)`` of the window (seconds from
    its start): ``tick`` is called from the driver's loop."""

    def __init__(self, logdir: str, start_s: float, length_s: float, spans: Spans):
        self.logdir, self.start_s, self.stop_s = logdir, start_s, start_s + length_s
        self.spans = spans
        self.state = "before"
        self._mark = None

    def tick(self, elapsed_s: float) -> None:
        import jax

        if self.state == "before" and elapsed_s >= self.start_s:
            jax.profiler.start_trace(self.logdir)
            self._mark = jax.profiler.TraceAnnotation(SPAN_PREFIX + "window")
            self._mark.__enter__()
            self.spans.recording = True
            self.state = "on"
        elif self.state == "on" and elapsed_s >= self.stop_s:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state != "on":
            return
        self.spans.recording = False
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"


# --------------------------------------------------------------- loading
def load(logdir: str) -> Dict[str, Any]:
    """The trace under ``logdir`` as plain data: ``{"devices": {id:
    {"ops": [[name, start_ns, dur_ns], ...], "modules": [...]}}, "spans":
    [[name, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no trace under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    devices: Dict[str, Dict[str, List]] = {}
    spans: List[List] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(m.group(1), {"ops": [], "modules": []})
            for line in plane.lines:
                key = ("ops" if line.name == "XLA Ops" else
                       "modules" if line.name == "XLA Modules" else None)
                if key is None:
                    continue
                # an op's event is named by its whole HLO instruction;
                                # its name is what precedes " = "
                dev[key].extend([ev.name.split(" = ")[0], float(ev.start_ns),
                                 float(ev.duration_ns)] for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([ev.name, float(ev.start_ns), float(ev.duration_ns)]
                             for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


# ------------------------------------------------------------- reduction
def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv: Sequence[Tuple[float, float]], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


class Reduced:
    """What one trace says, over the ``cb.window`` span."""

    def __init__(self, data: Dict[str, Any]):
        self.data = data
        win = [s for s in data["spans"] if s[0] == SPAN_PREFIX + "window"]
        if not win:
            raise ValueError("the trace has no cb.window span")
        self.lo = win[0][1]
        self.hi = win[0][1] + win[0][2]
        self.window_s = (self.hi - self.lo) / 1e9
        self.host = sorted((s[1], s[1] + s[2], s[0][len(SPAN_PREFIX):])
                           for s in data["spans"] if s[0] != SPAN_PREFIX + "window")
        self.busy: Dict[str, List[Tuple[float, float]]] = {}
        for dev, ev in data["devices"].items():
            src = ev["ops"] or ev["modules"]
            self.busy[dev] = _clip(_union((s, s + d) for _, s, d in src),
                                   self.lo, self.hi)

    @property
    def chips(self) -> List[str]:
        return sorted(d for d, iv in self.busy.items() if iv)

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the chips that ran."""
        chips = self.chips
        if not chips:
            return 0.0
        return sum(sum(e - s for s, e in self.busy[d]) for d in chips) / len(chips) / 1e9

    def programs(self, pattern: str) -> List[Tuple[str, float, float]]:
        """Module events (name, start_ns, dur_ns) in the window whose name
        matches ``pattern``, on every chip, in start order."""
        rx = re.compile(pattern)
        out = []
        for dev in self.chips:
            out.extend((n, s, d) for n, s, d in self.data["devices"][dev]["modules"]
                       if rx.search(n) and s >= self.lo and s + d <= self.hi)
        return sorted(out, key=lambda x: x[1])

    def gap_before(self, start_ns: float, dev: Optional[str] = None) -> float:
        """Idle nanoseconds between the end of the device's last busy
        interval and ``start_ns``."""
        dev = dev or self.chips[0]
        prev = [e for s, e in self.busy[dev] if e <= start_ns]
        return start_ns - max(prev) if prev else 0.0

    def idle_gaps(self) -> List[Tuple[float, float, str]]:
        """Idle stretches of the first chip in the window, each named by the
        host span that covers most of it (``host_idle`` where none does)."""
        if not self.chips:
            return []
        busy = self.busy[self.chips[0]]
        edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
                if edges[i + 1] > edges[i]]
        out = []
        for s, e in gaps:
            cover: Dict[str, float] = defaultdict(float)
            for hs, he, name in self.host:
                if he <= s:
                    continue
                if hs >= e:
                    break
                cover[name] += min(he, e) - max(hs, s)
            out.append((s, e, max(cover, key=cover.get) if cover else "host_idle"))
        return out

    def self_times(self, dev: str) -> Dict[str, float]:
        """Seconds per op name in the window, each op less the ops nested
        inside it (a loop's event holds its body's)."""
        evs = sorted(((s, -d, n) for n, s, d in
                      self.data["devices"][dev]["ops"] or self.data["devices"][dev]["modules"]
                      if s >= self.lo and s + d <= self.hi))
        out: Dict[str, float] = defaultdict(float)
        stack: List[Tuple[float, str]] = []          # (end, name) of open parents
        for s, neg, n in evs:
            d = -neg
            while stack and stack[-1][0] <= s:
                stack.pop()
            if stack:
                out[stack[-1][1]] -= d
            out[n] += d
            stack.append((s + d, n))
        return {n: v / 1e9 for n, v in out.items()}

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        ops: Dict[str, float] = defaultdict(float)
        for dev in self.chips:
            for n, v in self.self_times(dev).items():
                ops[n] += v / len(self.chips)
        gaps: Dict[str, float] = defaultdict(float)
        for s, e, name in self.idle_gaps():
            gaps[name] += (e - s) / 1e9
        key = lambda kv: -kv[1]
        return {"device_ops": [[n, v] for n, v in sorted(ops.items(), key=key)[:top]],
                "idle_gaps": [[n, v] for n, v in sorted(gaps.items(), key=key)[:top]]}


def trim(data: Dict[str, Any], lo_ns: float, hi_ns: float) -> Dict[str, Any]:
    """The part of ``data`` inside ``[lo_ns, hi_ns)``, with a ``cb.window``
    span over it: how a recorded trace is cut down to keep as test data."""
    keep = lambda evs: [e for e in evs if e[1] >= lo_ns and e[1] + e[2] <= hi_ns]
    return {"devices": {d: {k: keep(v) for k, v in ev.items()}
                        for d, ev in data["devices"].items()},
            "spans": keep([s for s in data["spans"] if s[0] != SPAN_PREFIX + "window"])
            + [[SPAN_PREFIX + "window", lo_ns, hi_ns - lo_ns]]}

