"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it makes the cell's traffic and weights from ``--seed``, builds
the program's fleet (``FleetSpec`` -> ``Fleet.from_spec`` on the host
clock), warms up the shapes the traffic uses, measures for ``--seconds``
(``--trace 1``: with the profiler on over part of the window and the
per-layer metrics instead of the end-to-end ones), reads the peak device
memory, frees the program's state, and checks a sample of the served tokens
against the float32 reference. The last line of stdout is one JSON object;
the last lines of stderr are the numbers compared, each with its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result. JAX's persistent compilation cache is kept in
``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` names one.
``CHIPBENCH_KEEP_TRACE=<file>`` keeps the first 0.3 s of a traced run's
trace as plain data (how ``chipbench/data`` was recorded).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, Optional, Sequence  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
for p in (REPO / "src", REPO):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import core, traffic  # noqa: E402
from chipbench.compilelog import CompileLog, memory_peak  # noqa: E402


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell needs."""


class Run:
    """Everything a driver and a metric reader see of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.notes: Dict[str, str] = {}
        self.profiler = None
        self.spans = None
        self.reduced = None

    def tick(self, elapsed_s: float) -> None:
        if self.profiler is not None:
            self.profiler.tick(elapsed_s)

    def note(self, key: str, text: str) -> None:
        self.notes[key] = text


def _compile_cache() -> str:
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _stats(fleet) -> Dict[str, int]:
    keys = ("decode_tokens", "decode_steps", "prefill_tokens", "prefill_calls")
    out = dict.fromkeys(keys, 0)
    for r in fleet.replicas:
        st = r.stats
        for k in keys:
            out[k] += getattr(st, k)
    return out


def _free_program_state() -> None:
    """Drop what the program keeps alive after its fleet is gone: its
    jitted-program caches and the registry that pins each params tree."""
    from repro.serving import pool

    pool.clear_program_caches()
    pool._PARAMS_TOKENS.clear()
    gc.collect()


def verdict(gap: Optional[float], tokens: int, limit: Optional[float],
            need: int) -> bool:
    """The run is correct when the widest logit gap is within the limit and
    enough served tokens were checked."""
    return (limit is not None and gap is not None and gap <= limit
            and tokens >= need)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, model: Optional[Dict] = None,
             mix: Optional[Dict] = None, reduced: bool = False,
             control: bool = False, check: bool = True,
             observe: Optional[Callable[[Run], Dict]] = None, log=print,
             t_start: float = T_START, manifest: Optional[Dict] = None) -> Dict[str, Any]:
    """One run of ``workload``; returns the result line as a dict.

    Tests drive the rest of a run on the CPU with ``require_tpu=False``, a
    small ``model`` and ``mix`` and ``reduced=True`` (the program's reduced
    config of the same architecture). Calibration only: ``control=True``
    puts the float8 control's first choices in place of the served tokens
    and decides ``correct`` from them by the same ``verdict`` (the
    program's own reading stays beside it as ``program_gap``);
    ``check=False`` skips the reference (the knee sweep); ``observe(run)``
    adds readings of the window under ``observed``."""
    man = manifest or core.manifest()
    c = core.cell(man, workload)
    model = model or core.load_config(man, c["config"])
    mix = mix or core.load_mix(c["traffic"])
    wk = core.work(model["work"])
    ref = core.reference(model["work"])
    drv = core.driver(mix["driver"])

    import jax

    if require_tpu:
        cache_dir = _compile_cache()
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < c["chips"]):
        print(f"chipbench: {workload} needs {c['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        raise NoChip(2)
    used = devices[:c["chips"]]
    kind = used[0].device_kind
    pk = core.peaks(kind) if require_tpu else core.peaks("TPU v5 lite")
    clog = CompileLog()

    from repro.configs import get_config, reduced_config
    from repro.core.energy import EnergyModel
    from repro.hw import chip_for_device_kind
    from repro.hw.chips import TPU_V5E
    from repro.models import abstract_params
    from repro.serving import ClockSpec, Fleet, FleetSpec, PoolSpec, ReplicaSpec

    from chipbench import weights

    batch = core.decode_batch(model, mix, wk)
    specs = traffic.make(mix, seed, seconds, model["vocab_size"])
    log(f"cell {workload}: {model['arch']}, decode batch {batch}, max_seq_len "
        f"{mix['max_seq_len']}, {mix['driver']}; traffic {traffic.summary(specs)}"
        + (f"; compile cache {cache_dir}" if require_tpu else ""))

    leaves = ref.leaves(model)
    params = weights.make_params(leaves, model["num_hidden_layers"], seed,
                                 model["torch_dtype"], wrap_stages=ref.wrap)
    jax.block_until_ready(params)
    cfg = reduced_config(model["arch"]) if reduced else get_config(model["arch"])
    want = abstract_params(cfg)
    if (jax.tree.structure(want) != jax.tree.structure(params)
            or any((a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                   zip(jax.tree.leaves(want), jax.tree.leaves(params)))):
        raise core.BenchError(f"the weights made for {model['arch']} do not match "
                              "the program's parameter tree")
    spec = FleetSpec(replicas=(ReplicaSpec(
        name="r0", arch=model["arch"], clock=ClockSpec(mode="lock"),
        decode=PoolSpec(batch=batch), max_seq_len=mix["max_seq_len"],
        reduced=reduced),), router="jsq")
    hw = chip_for_device_kind(kind) if require_tpu else TPU_V5E
    fleet = Fleet.from_spec(spec, emodel=EnergyModel(hw), clock=time.perf_counter,
                            params_for={model["arch"]: params})
    run = Run(fleet=fleet, specs=specs, mix=mix, seconds=float(seconds), batch=batch,
              model=model, wk=wk, peaks=pk, seed=seed)
    drv.warmup(run)
    if hasattr(drv, "ramp"):
        drv.ramp(run)
    jax.block_until_ready(fleet.replicas[0].decode_pool.cache)
    run.setup_s = time.perf_counter() - t_start
    compiles_before = clog.count()
    log(f"set-up {run.setup_s:.3f} s; {clog.line()}")

    tmp = None
    if trace:
        from chipbench import tracing

        tmp = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
        run.spans = tracing.Spans()
        run.spans.install()
        length = min(8.0, 0.5 * seconds)
        run.profiler = tracing.Profiler(tmp.name, 0.25 * seconds, length, run.spans)
    stats0 = _stats(fleet)
    try:
        run.window = drv.measure(run)
    finally:
        if trace:
            run.profiler.stop()
            run.spans.uninstall()
    stats1 = _stats(fleet)
    run.stats = {k: stats1[k] - stats0[k] for k in stats0}
    in_window = clog.count() - compiles_before
    log(f"window {run.window.seconds:.3f} s: {len(run.window.requests)} requests "
        f"submitted, {len(run.window.finished())} finished, {run.window.failed()} "
        f"failed; compilations inside the window: {in_window}; {clog.line()}")
    peak = memory_peak(used)
    device: Dict[str, Any] = {"platform": used[0].platform, "kind": kind,
                              "count": len(used), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        from chipbench import tracing

        if run.profiler.state == "done":
            data = tracing.load(tmp.name)
            run.reduced = tracing.Reduced(data)
            keep = os.environ.get("CHIPBENCH_KEEP_TRACE")
            if keep:
                lo = run.reduced.lo
                Path(keep).write_text(json.dumps(tracing.trim(data, lo, lo + 0.3e9)))
            device["busy_s"] = run.reduced.busy_s()
            device["window_s"] = run.reduced.window_s
            breakdown = run.reduced.breakdown()
        tmp.cleanup()

    kind_key = "per_layer" if trace else "end_to_end"
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in core.metrics_for(man, workload, kind_key):
        v = core.metric_reader(m["name"]).read(run)
        if v is None:
            if not trace:
                raise core.BenchError(f"end-to-end metric {m['name']} read nothing")
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    for k, text in run.notes.items():
        log(f"note {k}: {text}")

    finished = run.window.finished()
    attempted, failed = len(run.window.requests), run.window.failed()
    observed = observe(run) if observe is not None else None
    del run, fleet, params
    _free_program_state()
    out: Dict[str, Any] = {"correct": None, "attempted": attempted,
                           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if observed is not None:
        out["observed"] = observed
    if not check:
        return out

    from chipbench import check as chk_mod

    chk = mix["check"]
    reqs = chk_mod.sample(finished, seed, chk["tokens"], chk["requests"])
    t_ref = time.perf_counter()
    read = chk_mod.readings(model, ref, seed, reqs, control=control)
    limit = model["limits"]["max_logit_gap"]
    gap = read["control_gap"] if control else read["max_logit_gap"]
    compared = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "tokens_checked": {"value": read["tokens"], "limit": chk["tokens"]},
    }
    out["correct"] = verdict(gap, read["tokens"], limit, chk["tokens"])
    log(f"reference: {len(reqs)} requests, {read['tokens']} served tokens, "
        f"{time.perf_counter() - t_ref:.3f} s; share at the reference's argmax "
        f"{read.get('argmax_share')}"
        + (f"; the float8 control in the program's place (program's gap "
           f"{read['max_logit_gap']})" if control else ""))
    for name, nv in compared.items():
        bound = "at most" if name == "max_logit_gap" else "at least"
        print(f"check {name} {nv['value']} ({bound} {nv['limit']})", file=sys.stderr)
    if control:
        out["program_gap"] = read["max_logit_gap"]
    out["check"] = compared
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        return int(e.code)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
