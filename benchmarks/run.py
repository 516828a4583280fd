"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (and writes bench_output.txt is the
caller's job via tee).

Usage:  PYTHONPATH=src python -m benchmarks.run [--only SECTION[,SECTION]]
                                                [--json [DIR]]

``--only dse`` runs just the DSE sections (what the CI smoke step uses,
together with ``BENCH_BUDGET=small``); sections: paper, dse, workloads,
kernels, serve.

``--json [DIR]`` additionally persists each section's rows as
``BENCH_<section>.json`` (default DIR: the repository root) with the
``derived`` key=value pairs parsed out, so future sessions can assert
against a *recorded* trajectory instead of re-measuring ad hoc — e.g.
``BENCH_dse.json["rows"][i]["metrics"]["configs_per_s"]``.  Non-full
budgets write ``BENCH_<section>_<budget>.json`` (e.g.
``BENCH_dse_small.json``) so the recorded-baseline guard
(``benchmarks/baseline.py``) always compares like-for-like budgets.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
from typing import Dict, List

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def parse_derived(derived: str) -> Dict[str, object]:
    """``k=v;k=v`` -> dict, values parsed as float where they look like
    one (a trailing unit such as ``x`` or a ``a->b`` arrow keeps the raw
    string — the reader decides how to interpret those)."""
    out: Dict[str, object] = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = v
    return out


def write_json(section: str, rows: List[Dict], out_dir: str) -> str:
    """Persist one section's rows (with parsed metrics) as
    ``BENCH_<section>.json`` (full budget) or
    ``BENCH_<section>_<budget>.json`` under ``out_dir``; returns the
    path.  The budget suffix keeps smoke-tier snapshots separate from
    the full-budget trajectory — cross-budget throughput is not
    comparable (see ``benchmarks.baseline``)."""
    budget = os.environ.get("BENCH_BUDGET", "full") or "full"
    payload = {
        "section": section,
        "budget": budget,
        "rows": [{"name": r["name"],
                  "us_per_call": round(float(r["us_per_call"]), 3),
                  "derived": r["derived"],
                  "metrics": parse_derived(r["derived"])} for r in rows],
    }
    suffix = "" if budget == "full" else f"_{budget}"
    path = os.path.join(out_dir, f"BENCH_{section}{suffix}.json")
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return path


def main(argv: List[str] = None) -> int:
    from repro.compile_cache import enable_compile_cache

    from . import (bench_dse, bench_kernels, bench_paper, bench_serve,
                   bench_workloads)

    enable_compile_cache()

    sections = {"paper": bench_paper, "dse": bench_dse,
                "workloads": bench_workloads, "kernels": bench_kernels,
                "serve": bench_serve}
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of sections: "
                         + ",".join(sections))
    ap.add_argument("--json", nargs="?", const=str(REPO_ROOT), default=None,
                    metavar="DIR",
                    help="also write BENCH_<section>.json per section "
                         "(default DIR: repository root)")
    args = ap.parse_args(argv)
    if args.only:
        unknown = set(args.only.split(",")) - set(sections)
        if unknown:
            ap.error(f"unknown section(s) {sorted(unknown)}")
        names = args.only.split(",")
    else:
        names = list(sections)

    all_rows: List[Dict] = []
    for name in names:
        rows: List[Dict] = []
        sections[name].run(rows)
        if args.json is not None:
            path = write_json(name, rows, args.json)
            print(f"# wrote {path}", file=sys.stderr)
        all_rows.extend(rows)

    print("name,us_per_call,derived")
    for r in all_rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
