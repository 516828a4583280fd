#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: the plain
reference, computed in bfloat16 (the nearest precision below the float32
that the configuration states), put in the program's place and compared
exactly as a run compares the program.  Every cell has to read as not
correct under it.  It sets the upper reading of each limit.

    python3 bench/control.py --workload net28-sweep --seeds 1,2,3

It runs on the host, at the cell's own sizes: for a sweep cell the rows a
run samples from its blocks, for a serve cell the questions of the
window's schedule whose blocks a run samples.  The benchmark's own runs do
not run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import correct  # noqa: E402
import loads  # noqa: E402
from ranking import objectives, pareto_front  # noqa: E402


def low_precision():
    import ml_dtypes
    return ml_dtypes.bfloat16


def control_sweep(ref, low, config, traffic, check, seed, seconds=None):
    """The sweep numbers with ``low`` (a Reference in lower precision)
    answering for the program on the sampled rows of every block."""
    blocks = loads.sweep_blocks(config["knobs"], traffic, seed)
    rng = loads.rng_for(seed, 5)
    n = check["sample_rows"]
    lc, le = low.evaluate(np.ones((1, ref.K), np.float32))
    bc, be = ref.evaluate(np.ones((1, ref.K), np.float32))
    calls, ref_rows = [], {}
    for b, blk in enumerate(blocks):
        rows = np.sort(rng.choice(blk.shape[0], min(n, len(blk)),
                                  replace=False))
        cyc, en = ref.evaluate(blk[rows])
        ref_rows[b] = {"rows": np.arange(len(rows)), "cycles": cyc,
                       "energy": en, "cost": ref.cost(blk[rows])}
        c, e = low.evaluate(blk[rows])
        obj = objectives(c, e, ref.cost(blk[rows]), lc[0], le[0],
                         range(len(ref.cells)))
        calls.append({"block": b, "cycles": c, "latency": obj[:, 0],
                      "energy": obj[:, 1], "cost": obj[:, 2],
                      "pareto": pareto_front(obj)})
    return correct.sweep_numbers(calls, ref_rows, bc[0], be[0])


def control_serve(ref, low, config, traffic, check, seed, seconds):
    """The serve numbers with ``low`` ranking the schedule's questions
    whose blocks a run would sample."""
    names = [k["name"] for k in config["knobs"]]
    pool = loads.candidates(config["knobs"], int(traffic["pool"]),
                            loads.rng_for(seed, 2))
    cells = [(c.arch, c.workload) for c in ref.cells]
    cat = loads.catalog(cells, config["knobs"], traffic,
                        loads.rng_for(seed, 3))
    sched = loads.schedule(cat, traffic, seconds, loads.rng_for(seed, 4))
    sig = lambda q: tuple(sorted(q["overrides"].items()))
    asked = sorted({sig(q) for q in sched.questions})
    rng = loads.rng_for(seed, 6)
    rest = [s for s in asked if s]
    nb = check["sample_blocks"]
    pick = ([()] if () in asked else []) + [
        rest[i] for i in sorted(rng.choice(
            len(rest), min(len(rest), nb - (() in asked)), replace=False))]
    bc, be = ref.evaluate(np.ones((1, ref.K), np.float32))
    lc, le = low.evaluate(np.ones((1, ref.K), np.float32))
    blocks, answers, questions = {}, [], []
    for s in pick:
        cand = correct.pin(pool, names, dict(s))
        cyc, en = ref.evaluate(cand)
        blocks[s] = {"cand": cand, "cycles": cyc, "energy": en,
                     "cost": ref.cost(cand), "base_c": bc[0],
                     "base_e": be[0]}
        c, e = low.evaluate(cand)
        for q in sched.questions:
            if sig(q) != s:
                continue
            cols = correct.resolve(ref.cells, q["workload"], q["archs"])
            obj = objectives(c, e, ref.cost(cand), lc[0], le[0], cols)
            top = pareto_front(obj)[: q["top_k"]]
            rel = c[:, cols] / lc[0][cols]
            lead = int(top[0])
            designs = [SimpleNamespace(
                theta=tuple(float(v) for v in cand[i]),
                latency=float(obj[i, 0]), energy=float(obj[i, 1]),
                cost=float(obj[i, 2]),
                cycles=tuple(float(v) for v in c[i, cols])) for i in top]
            answers.append(SimpleNamespace(
                designs=designs, cells=[ref.cells[i].name for i in cols],
                best_arch=ref.cells[cols[int(np.argmin(rel[lead]))]].arch))
            questions.append(q)
    return correct.serve_numbers(
        answers, questions, lambda ov: blocks[tuple(sorted(ov.items()))],
        ref.cells, 0)


CONTROLS = {"sweep": control_sweep, "serve": control_serve}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (serve cells; default run_seconds)")
    args = ap.parse_args(argv)
    import run as bench

    cell, manifest, config, traffic, check = bench.load_cell(args.workload)
    seconds = args.seconds or manifest["run_seconds"]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(bench.CACHE_DIR))
    from export import plain_cells
    from reference import Reference

    ex = bench.build_explorer(config)
    bench.check_config(ex, config)
    cells = plain_cells(ex)
    ref = Reference(cells, config)
    low = Reference(cells, config, dtype=low_precision())
    limits = check["limits"]
    for seed in (int(s) for s in args.seeds.split(",")):
        nums = CONTROLS[traffic["kind"]](ref, low, config, traffic, check,
                                         seed, seconds)
        fails = sorted(k for k in nums if k in limits
                       and not nums[k] <= limits[k])
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "numbers": nums, "fails": fails}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
