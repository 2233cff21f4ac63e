"""The harness core: the manifest, name resolution, percentiles, peaks.

Nothing here lists configurations, traffic mixes or metrics. A cell of
``BENCHMARK.json`` names a configuration and a traffic mix; each is a file
found by that name:

* ``configs/<config>.json``   the configuration as it is run;
* ``traffic/<mix>.json``      the traffic mix, which names its ``driver``;
* ``drivers/<driver>.py``     the code that offers that traffic;
* ``metrics/<metric>.py``     one reader per per-layer metric;
* ``work/<work>.py``          FLOPs and bytes of a call, from shapes;
* ``reference/<work>.py``     the plain float32 reference of the model.
"""
from __future__ import annotations

import importlib.util
import json
import math
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
MANIFEST = REPO / "BENCHMARK.json"


class BenchError(RuntimeError):
    """A fault of the benchmark's inputs: a missing file, an unknown name."""


def load_json(path: Path) -> Any:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise BenchError(f"no such file: {path}") from None


def manifest(path: Optional[Path] = None) -> Dict[str, Any]:
    return load_json(path or MANIFEST)


def cell(man: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload named {name!r} in the manifest")


def config_entry(man: Dict[str, Any], name: str) -> Dict[str, Any]:
    for c in man["configs"]:
        if c["name"] == name:
            return c
    raise BenchError(f"no config named {name!r} in the manifest")


def load_config(man: Dict[str, Any], name: str, root: Path = REPO) -> Dict[str, Any]:
    return load_json(root / config_entry(man, name)["file"])


def load_mix(name: str, base: Path = HERE) -> Dict[str, Any]:
    return load_json(base / "traffic" / f"{name}.json")


_MODULES: Dict[Path, Any] = {}


def load_module(path: Path) -> Any:
    """Import the file at ``path`` under a name made from its path (file
    names may hold dots, as metric names do)."""
    path = Path(path).resolve()
    if path in _MODULES:
        return _MODULES[path]
    if not path.exists():
        raise BenchError(f"no such file: {path}")
    modname = "chipbench_" + "_".join(
        "".join(ch if ch.isalnum() else "_" for ch in part)
        for part in path.relative_to(HERE.parent).with_suffix("").parts)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


def driver(name: str, base: Path = HERE) -> Any:
    return load_module(base / "drivers" / f"{name}.py")


def metric_reader(name: str, base: Path = HERE) -> Any:
    return load_module(base / "metrics" / f"{name}.py")


def work(name: str, base: Path = HERE) -> Any:
    return load_module(base / "work" / f"{name}.py")


def reference(name: str, base: Path = HERE) -> Any:
    return load_module(base / "reference" / f"{name}.py")


def metrics_for(man: Dict[str, Any], cell_name: str, kind: str) -> List[Dict[str, Any]]:
    """The manifest's ``end_to_end`` or ``per_layer`` metrics this cell
    reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in man[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


# ------------------------------------------------------------ arithmetic
def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile (numpy's default method), ``q`` in
    percent. Raises on an empty sample: a tail of nothing is no number."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise BenchError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def peaks(device_kind: str, base: Path = HERE) -> Dict[str, Any]:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = load_json(base / "peaks.json")
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r}; "
                         f"have {sorted(table)}")
    return table[device_kind]


def least_time_s(flops: float, nbytes: float, pk: Dict[str, Any]) -> float:
    """The least time the chip could take for ``flops`` and ``nbytes``."""
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def bound_of(flops: float, nbytes: float, pk: Dict[str, Any]) -> str:
    return ("compute" if flops / pk["bf16_flops_per_s"]
            >= nbytes / pk["hbm_bytes_per_s"] else "memory")


def decode_batch(model: Dict[str, Any], mix: Dict[str, Any], wk: Any) -> int:
    """The largest power of two whose cache at the mix's ``max_seq_len``
    fits the configuration's ``cache_bytes``."""
    per_seq = wk.cache_bytes_per_token(model) * mix["max_seq_len"]
    n = model["cache_bytes"] // per_seq
    if n < 1:
        raise BenchError("one sequence's cache exceeds cache_bytes")
    return 1 << (int(n).bit_length() - 1)


# ---------------------------------------------------------------- window
class Window:
    """What a driver hands back: the requests it submitted, when the
    measured window opened (``t0``, host clock), how long it lasted, and
    when the driver stopped serving (``closed_s``: after an open loop's
    drain, made when the driver hands the window back)."""

    def __init__(self, *, t0: float, seconds: float, requests: List[Any],
                 open_loop: bool):
        self.t0, self.seconds, self.requests = t0, float(seconds), requests
        self.open_loop = open_loop
        self.closed_s = time.perf_counter()

    @property
    def t1(self) -> float:
        return self.t0 + self.seconds

    def stamps(self, req) -> List[float]:
        """Every token's stamp: the first token, then each decode token."""
        lg = req.ledger
        return ([lg.first_token_s] if lg.first_token_s is not None else []) + list(lg.token_s)

    def counted(self) -> List[Any]:
        """The requests the window's tails are over: in an open loop every
        request (all were due in the window); in a closed loop every request
        that produced a token in it."""
        if self.open_loop:
            return list(self.requests)
        return [r for r in self.requests
                if any(self.t0 <= t < self.t1 for t in self.stamps(r))]

    def finished(self) -> List[Any]:
        return [r for r in self.requests if r.done]

    def failed(self) -> int:
        """Open loop: requests due in the window that did not finish."""
        return sum(not r.done for r in self.requests) if self.open_loop else 0
