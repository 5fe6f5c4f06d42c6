"""Find the knee of a server cell: the highest offered rate that the
server keeps up with.

    python3 -m benchmarks.chip.knee_sweep --workload <server cell> --rates 500,1000,2000 --seconds 8

One process builds the cell once, then offers each rate for ``--seconds``
through the cell's own driver and prints one row per rate: offered rate,
completed rate inside the window, requests still queued when the window
closed, p99 latency and the generator's p99 lag.  The knee is the highest
rate whose completed rate keeps up and whose queue does not grow; the cell
runs at a fixed fraction of it, written into its traffic file.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from . import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m benchmarks.chip.knee_sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated offered rates, requests/s")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    spec = json.loads(run.SPEC.read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    config = run.load_named(run.HERE, "configs", cell["config"])
    traffic = run.load_named(run.HERE, "traffic", cell["traffic"]) | {"rate_rps": max(rates)}
    driver = run.load_module(run.HERE / "drivers" / f"{traffic['driver']}.py")
    family = run.load_family(run.HERE, config)

    sys.path.insert(0, str(run.CHECKOUT / "src"))
    device = run.device_gate(cell["chips"])
    run.use_compile_cache()
    from .spans import Spans

    rng = np.random.default_rng(args.seed)
    _, requests = family.inputs(config, traffic, rng)
    state = driver.setup(family.build(config)[0], traffic, requests, Spans(annotate=False))
    rows = []
    try:
        for rate in rates:
            state["traffic"] = traffic | {"rate_rps": rate}
            res = driver.window(state, args.seconds, rng)
            t0, t1 = res["window"]
            row = {
                "offered_rps": rate,
                "completed_rps": res["completed_in_window"] / (t1 - t0),
                "queue_at_end": res["queue_at_end"],
                "p99_ms": float(np.quantile(res["latencies_s"], 0.99, method="inverted_cdf")) * 1e3,
                "gen_lag_p99_ms": float(np.quantile(res["lags_s"], 0.99, method="inverted_cdf")) * 1e3,
                "failed": res["failed"],
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        driver.close(state)
    print(json.dumps({"workload": cell["name"], "device": device, "seconds": args.seconds, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
