"""The serving path's one span recorder.

Off by default. ``enable()`` turns it on; then ``span(name, uid)`` opens a
``jax.profiler.TraceAnnotation("repro." + name)``, so the span lands in the
profiler's host plane on the same clock as the device's ops, and appends
``(name, t0_ns, t1_ns, parent, uid)`` to an in-memory list of at most
``CAP`` records, timed with ``time.perf_counter_ns``. ``parent`` is the
index in that list of the span open around this one (-1 at the top); a
span is recorded when it opens, so a parent's index is always lower than
its children's, and its ``t1_ns`` stays ``None`` until it closes. ``uid``
says what the span works on: the request's uid where it works for one
request (a request's ``prefill`` and ``place`` share it), the padded group
size for ``decode.fused``.

Off, ``span`` returns one shared null context: no annotation is built, no
clock is read, nothing is appended. ``records()`` hands back the list and
``clear()`` empties it; there is no other exporter. The serving loop is
single-threaded, and so is the recorder's stack of open spans.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, List, Optional, Tuple

import jax

PREFIX = "repro."
CAP = 1 << 20            # records kept; later spans are counted in ``dropped``

Record = Tuple[str, int, Optional[int], int, Any]

_NULL = contextlib.nullcontext()
_on = False
_records: List[Record] = []
_open: List[int] = []     # indices of the spans open now, innermost last
dropped = 0


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def enabled() -> bool:
    return _on


def records() -> List[Record]:
    return _records


def clear() -> None:
    global dropped
    _records.clear()
    _open.clear()
    dropped = 0


class _Span:
    __slots__ = ("name", "uid", "ann", "index")

    def __init__(self, name: str, uid: Any):
        self.name, self.uid = name, uid

    def __enter__(self):
        global dropped
        self.ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
        self.ann.__enter__()
        if len(_records) < CAP:
            self.index = len(_records)
            _records.append((self.name, time.perf_counter_ns(), None,
                             _open[-1] if _open else -1, self.uid))
            _open.append(self.index)
        else:
            self.index = -1
            dropped += 1
        return self

    def __exit__(self, *exc):
        # a ``clear()`` while this span was open left nothing to close
        if _open and _open[-1] == self.index:
            t1 = time.perf_counter_ns()
            _open.pop()
            name, t0, _, parent, uid = _records[self.index]
            _records[self.index] = (name, t0, t1, parent, uid)
        self.ann.__exit__(*exc)
        return False


def span(name: str, uid: Any = None):
    """A context manager around one part of the serving path: the shared
    null context while the recorder is off."""
    if not _on:
        return _NULL
    return _Span(name, uid)
