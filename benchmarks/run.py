"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).

  PYTHONPATH=src python -m benchmarks.run [--only fig7,table3,...] [--target gap9]
                                          [--list-targets] [--json [PATH]]
                                          [--repeat N] [--aot]

``--target`` takes any registered target name (``repro.targets.registry``,
see ``list_targets()``) and is forwarded to every benchmark whose ``run``
accepts one (``dispatch_scaling``, ``compiled_e2e``,
``calibration_accuracy``, ``dispatch_overhead``, ``obs_overhead``) — the
per-figure benches
are pinned to the paper's published SoCs.  ``--aot`` is forwarded to
benches that compare the whole-graph AOT executable against the
per-segment path (``compiled_e2e``).  ``--list-targets`` prints every registered
target (plugins included) and exits; ``--json`` additionally collects the
emitted rows into one machine-readable summary (written to PATH, or
printed as a final ``benchmarks JSON:`` line when no PATH is given).
"""

from __future__ import annotations

import argparse
import inspect
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument(
        "--target",
        default="",
        help="registered target name for the target-generic benchmarks",
    )
    ap.add_argument(
        "--list-targets",
        action="store_true",
        help="print every registered target (plugins included) and exit",
    )
    ap.add_argument(
        "--repeat",
        type=int,
        default=0,
        metavar="N",
        help="measurement rounds for benches that take medians "
        "(pipeline_throughput); 0 keeps each bench's default",
    )
    ap.add_argument(
        "--aot",
        action="store_true",
        help="also run the whole-graph AOT executable in benches that "
        "support it (compiled_e2e) and assert it beats per-segment dispatch",
    )
    ap.add_argument(
        "--json",
        nargs="?",
        const="-",
        default=None,
        metavar="PATH",
        help="collect results as JSON (to PATH, or stdout when bare)",
    )
    args = ap.parse_args()

    from repro.backend.compile_cache import use_compile_cache

    use_compile_cache()

    if args.list_targets:
        from repro.targets import list_targets, target_info

        for name in list_targets():
            info = target_info(name)
            aliases = f" (aliases: {', '.join(info['aliases'])})" if info["aliases"] else ""
            print(f"{name:<12s} [{info['source']}]{aliases} {info['description']}")
        return

    if args.target:
        from repro.targets import get_target

        get_target(args.target)  # fail fast on unknown names

    from . import (
        calibration_accuracy,
        common,
        compiled_e2e,
        dispatch_overhead,
        dispatch_scaling,
        fig7_diana_micro,
        fig8_gap9_micro,
        fig9_10_l1_scaling,
        fig11_resnet_mapping,
        fuzz_coverage,
        obs_overhead,
        pipeline_throughput,
        pod_roofline_summary,
        serve_load,
        table3_e2e,
        table4_heterogeneity,
        tpu_kernel_schedules,
    )

    benches = {
        "fig7": fig7_diana_micro,
        "fig8": fig8_gap9_micro,
        "table3": table3_e2e,
        "table4": table4_heterogeneity,
        "fig9_10": fig9_10_l1_scaling,
        "fig11": fig11_resnet_mapping,
        "dispatch_scaling": dispatch_scaling,
        "dispatch_overhead": dispatch_overhead,
        "compiled_e2e": compiled_e2e,
        "calibration_accuracy": calibration_accuracy,
        "pipeline_throughput": pipeline_throughput,
        "serve_load": serve_load,
        "obs_overhead": obs_overhead,
        "fuzz_coverage": fuzz_coverage,
        "tpu_kernels": tpu_kernel_schedules,
        "pod_roofline": pod_roofline_summary,
    }
    only = {s.strip() for s in args.only.split(",") if s.strip()}
    print("name,us_per_call,derived")
    results: dict[str, dict] = {}
    failures = 0
    for name, mod in benches.items():
        if only and name not in only:
            continue
        kwargs = {}
        sig = inspect.signature(mod.run).parameters
        if args.target and "target" in sig:
            kwargs["target"] = args.target
        if args.repeat > 0 and "repeat" in sig:
            kwargs["repeat"] = args.repeat
        if args.aot and "aot" in sig:
            kwargs["aot"] = True
        common.drain_rows()
        try:
            mod.run(**kwargs)
            results[name] = {"ok": True, "rows": common.drain_rows()}
        except Exception as e:  # keep the suite going, report at the end
            failures += 1
            print(f"{name},0.0,ERROR={type(e).__name__}:{e}", flush=True)
            results[name] = {
                "ok": False,
                "error": f"{type(e).__name__}: {e}",
                "rows": common.drain_rows(),
            }
    if args.json is not None:
        payload = json.dumps({"target": args.target, "benches": results}, sort_keys=True)
        if args.json == "-":
            print(f"benchmarks JSON: {payload}", flush=True)
        else:
            with open(args.json, "w") as f:
                f.write(payload)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
