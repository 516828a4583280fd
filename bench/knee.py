#!/usr/bin/env python3
"""Find the highest rate a serve cell sustains: run the cell's traffic at
a ladder of rates, in one process on one chip, and print for each rate the
median and 95th-percentile latency, the failures, and whether the backlog
grew (the mean latency of the window's last quarter against its first).

    python3 bench/knee.py --workload net28-serve --seed 1 --seconds 30 \
        --rates 2,4,6,8,12

The cell's fixed rate is set at about 0.8 of the highest rate whose
backlog does not grow and whose tail does not break away; rerun this when
a cell's rate has been overtaken.  Like ``run.py`` it needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated queries per second, ascending")
    args = ap.parse_args(argv)
    cell, _, config, traffic, check = bench.load_cell(args.workload)
    if traffic["kind"] != "serve":
        raise SystemExit(f"{args.workload} is not a serve cell")
    import jax

    devs = jax.devices()
    bench.log(f"device: platform={devs[0].platform} "
              f"device_kind={devs[0].device_kind} count={len(devs)}")
    if devs[0].platform != "tpu":
        bench.log("knee: no TPU (there is no CPU fallback)")
        return 1
    jax.config.update("jax_compilation_cache_dir", str(bench.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clog = bench.CompileLog()
    jax.monitoring.register_event_duration_secs_listener(clog)
    ex = bench.build_explorer(config)
    bench.check_config(ex, config)
    points = []
    for rate in (float(r) for r in args.rates.split(",")):
        out = {"check_params": check}
        ns = argparse.Namespace(seed=args.seed, seconds=args.seconds,
                                trace=0)
        t = time.perf_counter()
        bench.run_serve(ex, config, dict(traffic, rate_qps=rate), ns, clog,
                        bench.Tracer(False), out)
        recs = out["run"]["records"]
        q = len(recs) // 4
        lat = np.asarray([r.done - r.due for r in recs if r.ok])
        first = np.mean([r.done - r.due for r in recs[:q] if r.ok] or [0])
        last = np.mean([r.done - r.due for r in recs[-q:] if r.ok] or [0])
        point = {"rate_qps": rate, "queries": len(recs),
                 "failed": out["failed"],
                 "p50_ms": out["e2e"]["query_p50_ms"],
                 "p95_ms": out["e2e"]["query_p95_ms"],
                 "max_ms": float(lat.max() * 1e3) if lat.size else None,
                 "growth": float(last / first) if first > 0 else None,
                 "wall_s": time.perf_counter() - t}
        points.append(point)
        print(json.dumps(point), flush=True)
    print(json.dumps({"workload": args.workload, "points": points}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
