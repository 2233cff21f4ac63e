"""Benchmark runner — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (the harness contract) and
writes per-table artefacts to results/benchmarks/*.csv.
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        fig1_roofline,
        fig2_heatmaps,
        fig3_pareto,
        fig4_request_energy,
        hypotheses_bench,
        kernels_micro,
        policy_bench,
        roofline_report,
        serve_autoscale,
        serve_cluster,
        serve_events,
        serve_fleet,
        serve_prefix,
        serve_scale,
        serve_trace,
        table1_power_cap,
        tpu_native,
    )

    # (module, kwargs, tag): kwargs reach mod.run() — the serve_scale entry
    # runs twice, once per replica-axis mode (batched vmap vs tuple-of-K)
    benches = [
        (table1_power_cap, {}, ""),
        (fig1_roofline, {}, ""),
        (fig2_heatmaps, {}, ""),
        (fig3_pareto, {}, ""),
        (fig4_request_energy, {}, ""),
        (hypotheses_bench, {}, ""),
        (policy_bench, {}, ""),
        (serve_cluster, {}, ""),
        (serve_trace, {}, ""),
        (serve_fleet, {}, ""),
        (serve_autoscale, {}, ""),
        (serve_events, {}, ""),
        (serve_scale, {"batched": True}, "batched"),
        (serve_scale, {"batched": False}, "unbatched"),
        (serve_prefix, {}, ""),
        (tpu_native, {}, ""),
        (kernels_micro, {}, ""),
        (roofline_report, {}, ""),
    ]
    print("name,us_per_call,derived")
    failed = 0
    for mod, kwargs, tag in benches:
        label = f"{mod.__name__}[{tag}]" if tag else mod.__name__
        try:
            for name, us, derived in mod.run(**kwargs):
                print(f"{name},{us:.1f},{derived}")
        except Exception as e:  # noqa: BLE001
            failed += 1
            print(f"{label},-1,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(f"{failed} benchmarks failed")


if __name__ == "__main__":
    main()
