#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  It names a
configuration (``bench/configs/<config>.json``: what the ``Explorer`` builds
and the model's knobs and energy tables, which the plain reference uses)
and a traffic mix (``bench/traffic/<traffic>.json``: the parameters of one
of the generators in ``loads.py``).  ``bench/cells/<cell>.json`` holds the
limits of the numbers that decide ``correct``.  Per-layer metrics are read
by ``bench/metrics/<metric>.py``.  New cells, configurations, mixes and
metrics are new files plus new ``BENCHMARK.json`` entries.

Set-up runs from the start of the process to the start of the window: the
imports, the ``Explorer`` build with its batch-1 compile, and the warm-up
of this cell's own shapes.  The window then runs for ``--seconds``.  With
``--trace 1`` a profiler trace of a steady stretch of the window gives the
per-layer metrics; otherwise the end-to-end metrics are printed.  After the
window the device's peak memory is read, the service is stopped, and what
the window produced is compared with the plain reference.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its limit).
Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result: there is no CPU fallback.
"""

from __future__ import annotations

import time

T_FIRST = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import correct  # noqa: E402
import loads  # noqa: E402
import profile_trace as tr  # noqa: E402
from compile_log import CompileLog  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"     # fixed, inside the checkout
TRACE_START = 0.3                   # traced stretch: share of the window
TRACE_SPAN = 0.4
GRACE_S = 60.0                      # wait for answers past the window


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def process_age() -> float:
    """Seconds since this process started (falls back to the first line
    of this file where /proc is not there)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - T_FIRST


def load_cell(name: str, root: Path = ROOT):
    """(cell entry, manifest, config, traffic, check) of a cell; ``check``
    holds the limits of the compared numbers and the sample sizes."""
    bench = root / "bench"
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (bench / "traffic" / f"{cell['traffic']}.json").read_text())
    check = json.loads((bench / "cells" / f"{name}.json").read_text())
    return cell, manifest, config, traffic, check


def metric_names(manifest, cell_name: str, section: str):
    """The ``section`` metrics this cell reports, in manifest order.  A
    per-layer metric without a ``workloads`` key goes with every cell that
    reports the end-to-end metric it moves."""
    e2e = [m["name"] for m in manifest["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if section == "end_to_end":
        return e2e
    return [m["name"] for m in manifest["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and ("workloads" in m or m["moves"] in e2e)]


def metric_file(name: str, bench: Path = BENCH) -> Path:
    """``bench/metrics/<name>.py``; a metric split by the end-to-end
    metric it moves (``<quantity>.<part>``) falls back to the quantity's
    one reader, ``bench/metrics/<quantity>.py``."""
    path = bench / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = bench / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    return path


def read_metric(name: str, run: dict, bench: Path = BENCH):
    """Call the metric's reader's ``read(run)``."""
    path = metric_file(name, bench)
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + name)


def wrap_span(obj, attr: str, name: str) -> None:
    """Record a host span around every call of ``obj.attr`` (traced runs
    only: the end-to-end runs call the program untouched)."""
    fn = getattr(obj, attr)

    def wrapped(*a, **k):
        with span(name):
            return fn(*a, **k)

    setattr(obj, attr, wrapped)


class Tracer:
    """Traces the stretch [start, start + length) of the window (seconds
    after ``t0``) and snapshots ``snap()`` at both ends."""

    def __init__(self, enabled: bool, snap=lambda: None):
        self.enabled = enabled
        self.snap = snap
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if enabled else None
        self.on = False
        self.done = False
        self.snaps = [None, None]
        self.t = [None, None]

    def start(self):
        import jax
        self.snaps[0] = self.snap()
        jax.profiler.start_trace(self.dir)
        self.t[0] = time.perf_counter()
        self.on = True

    def stop(self):
        import jax
        self.t[1] = time.perf_counter()
        jax.profiler.stop_trace()
        self.snaps[1] = self.snap()
        self.on, self.done = False, True

    def poll(self, elapsed: float, seconds: float) -> None:
        """Start or stop between two calls of a closed loop."""
        if not self.enabled or self.done:
            return
        if not self.on and elapsed >= TRACE_START * seconds:
            self.start()
        elif self.on and elapsed >= (TRACE_START + TRACE_SPAN) * seconds:
            self.stop()

    def run_in_thread(self, t0: float, seconds: float) -> threading.Thread:
        """For an open loop: a thread that starts and stops on time."""
        def body():
            time.sleep(max(0.0, t0 + TRACE_START * seconds
                           - time.perf_counter()))
            self.start()
            time.sleep(TRACE_SPAN * seconds)
            self.stop()
        th = threading.Thread(target=body, daemon=True)
        th.start()
        return th

    def summary(self) -> dict:
        ev = tr.load(self.dir)
        lo = ev["t_start_ns"] if ev["t_start_ns"] is not None else 0.0
        hi = lo + (self.t[1] - self.t[0]) * 1e9
        starts = [s for evs in ev["devices"].values() for _, s, _ in evs]
        if starts and not any(lo <= s < hi for s in starts):
            # device clock not on the host's: the stretch starts with the
            # first device operation and keeps the host-measured length
            log("trace: no device operation inside the host's stretch; "
                "aligning the stretch to the first device operation")
            lo = min(starts)
            hi = lo + (self.t[1] - self.t[0]) * 1e9
        out = tr.summarize(ev["devices"], ev["spans"], lo, hi)
        out["launches"] = tr.count_modules(ev["modules"], lo, hi)
        return out

    def cleanup(self):
        if self.dir:
            import shutil
            shutil.rmtree(self.dir, ignore_errors=True)


def build_explorer(config):
    """The ``Explorer`` the configuration states: the program's operator
    matrix, plus each of ``networks`` lowered onto each of
    ``network_archs`` that maps it."""
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.aidg.explorer import Explorer, default_scenarios

    spec = config["explorer"]
    cells = default_scenarios()
    if spec.get("networks"):
        from repro.core.network import default_network_scenarios
        cells += default_network_scenarios(
            networks=spec["networks"], archs=spec["network_archs"])
    return Explorer(cells)


def check_config(ex, config) -> None:
    """The program must serve the matrix and design space the
    configuration file states."""
    names = [cs.name for cs in ex.compiled]
    if names != config["cells"]:
        raise SystemExit(f"the program's matrix {names} differs from the "
                         f"configuration's {config['cells']}")
    knobs = [dict(name=k.name, lo=float(k.lo), hi=float(k.hi), ops=k.ops,
                  storages=k.storages) for k in ex.space.knobs]
    if knobs != config["knobs"]:
        raise SystemExit(f"the program's design space {knobs} differs from "
                         f"the configuration's {config['knobs']}")


def percentile(x, q) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q))


# -- the two kinds of cell ------------------------------------------------


def run_sweep(ex, config, traffic, args, clog, tracer, out):
    blocks = loads.sweep_blocks(config["knobs"], traffic, args.seed)
    t = time.perf_counter()
    ex.explore(blocks[0])
    clog.report(f"warm-up: explore, batch {blocks[0].shape[0]}",
                time.perf_counter() - t, sys.stderr)
    packed = ex.packed_matrix().stats()
    log(f"packed matrix: rows={packed['rows']} buckets={packed['buckets']} "
        f"scan_len={packed['scan_len']} cells={packed['cells']}")
    if tracer.enabled:
        wrap_span(ex, "evaluate_full", "evaluate_full")
    calls, n_before = [], clog.total_compiles
    out["setup_s"] = process_age()
    t0 = time.perf_counter()
    in_stretch = 0
    while True:
        elapsed = time.perf_counter() - t0
        if elapsed >= args.seconds:
            break
        tracer.poll(elapsed, args.seconds)
        b = len(calls) % len(blocks)
        if tracer.enabled:
            with span("explore"):
                res = ex.explore(blocks[b])
        else:
            res = ex.explore(blocks[b])
        in_stretch += tracer.on
        calls.append({"block": b, "cycles": res.cycles,
                      "latency": res.latency, "energy": res.energy,
                      "cost": res.cost, "pareto": res.pareto})
    t_end = time.perf_counter()
    if tracer.on:
        tracer.stop()
    window = t_end - t0
    n_cells = len(ex.compiled)
    rows = sum(blocks[c["block"]].shape[0] for c in calls)
    log(f"window: {len(calls)} explore calls of {blocks[0].shape[0]} "
        f"candidates x {n_cells} cells in {window:.3f} s; compiles in the "
        f"window: {clog.total_compiles - n_before}")
    out["e2e"] = {"configs_per_s": rows * n_cells / window}
    out["attempted"], out["failed"] = len(calls), 0
    out["run"] = {"kind": "sweep", "calls": len(calls), "window_s": window,
                  "calls_in_stretch": in_stretch, "packed_stats": packed}
    out["check"] = lambda ref, cells: check_sweep(
        calls, blocks, ref, args.seed, out["check_params"]["sample_rows"])


def check_sweep(calls, blocks, ref, seed, sample_rows):
    rng = loads.rng_for(seed, 5)
    ref_rows = {}
    t = time.perf_counter()
    base_c, base_e = ref.evaluate(np.ones((1, ref.K), np.float32))
    for b in sorted({c["block"] for c in calls}):
        blk = blocks[b]
        rows = np.sort(rng.choice(blk.shape[0], min(sample_rows, len(blk)),
                                  replace=False))
        cyc, en = ref.evaluate(blk[rows])
        ref_rows[b] = {"rows": rows, "cycles": cyc, "energy": en,
                       "cost": ref.cost(blk[rows])}
    log(f"reference: {len(ref_rows)} block(s) x {sample_rows} sampled rows "
        f"in {time.perf_counter() - t:.3f} s")
    return correct.sweep_numbers(calls, ref_rows, base_c[0], base_e[0])


def run_serve(ex, config, traffic, args, clog, tracer, out):
    from repro.serve import DSEService, Query, ServeClient, ServeFrontend

    pool_n = int(traffic["pool"])
    pool = loads.candidates(config["knobs"], pool_n,
                            loads.rng_for(args.seed, 2))
    kw = dict(candidates=pool, chunk=pool_n,
              max_batch=int(traffic["max_batch"]),
              window_s=float(traffic["window_s"]))
    os.environ.pop("SERVE_FAULT_PLAN", None)
    t = time.perf_counter()
    with DSEService(ex, **kw) as warm:         # shares the jit cache
        warm.query(Query.make())
    clog.report(f"warm-up: one pool dispatch, batch {pool_n}",
                time.perf_counter() - t, sys.stderr)
    cells = [(cs.arch, cs.workload) for cs in ex.compiled]
    cat = loads.catalog(cells, config["knobs"], traffic,
                        loads.rng_for(args.seed, 3))
    sched = loads.schedule(cat, traffic, args.seconds,
                           loads.rng_for(args.seed, 4))
    svc = DSEService(ex, **kw)
    fe = ServeFrontend(svc, max_inflight=int(traffic["max_inflight"]))
    try:
        t = time.perf_counter()
        with ServeClient(fe.address) as c:
            for q in sched.warm:
                c.query(Query.from_payload(q))
        clog.report(f"set-up questions: {len(sched.warm)} answered",
                    time.perf_counter() - t, sys.stderr)
        if tracer.enabled:
            wrap_span(fe, "_handle", "frontend")
            wrap_span(svc, "_answer_packed", "exact_tier")
            wrap_span(svc, "_rank", "rank")
            wrap_span(ex, "evaluate_full", "evaluate_full")
        tracer.snap = svc.stats
        loop = loads.OpenLoop(
            make_client=lambda a: ServeClient(a, io_timeout_s=GRACE_S
                                              + args.seconds),
            make_query=Query.from_payload, clients=int(traffic["clients"]),
            span=span if tracer.enabled else None)
        loop.open(fe.address, sched)
        n_before = clog.total_compiles
        st0 = svc.stats()
        t0 = time.perf_counter()
        out["setup_s"] = process_age()
        th = tracer.run_in_thread(t0, args.seconds) if tracer.enabled \
            else None
        recs = loop.run(sched, t0, GRACE_S)
        if th is not None:
            th.join()
        st1 = svc.stats()
    finally:
        fe.close()
        svc.close()
    ok = [r for r in recs if r.ok]
    worst = args.seconds + GRACE_S       # a failed query misses every limit
    lat = [(r.done - r.due) if r.ok else worst for r in recs]
    late = [r.sent - r.due for r in recs if r.sent == r.sent]
    log(f"window: {len(recs)} queries due in {args.seconds} s "
        f"({int(sched.new.sum())} new), {len(ok)} answered, "
        f"{len(recs) - len(ok)} failed; compiles in the window: "
        f"{clog.total_compiles - n_before}")
    if late:
        log(f"generator lateness: median {np.median(late) * 1e3:.3f} ms, "
            f"p95 {percentile(late, 95) * 1e3:.3f} ms, "
            f"max {max(late) * 1e3:.3f} ms")
    for r in recs:
        if not r.ok:
            log(f"query {r.index} failed: {r.error}")
            break
    out["e2e"] = {"query_p50_ms": percentile(lat, 50) * 1e3,
                  "query_p95_ms": percentile(lat, 95) * 1e3}
    log(f"latency over {len(lat)} queries: p50 "
        f"{out['e2e']['query_p50_ms']:.3f} ms, p95 "
        f"{out['e2e']['query_p95_ms']:.3f} ms")
    out["attempted"], out["failed"] = len(recs), len(recs) - len(ok)
    out["run"] = {"kind": "serve", "pool": pool_n, "records": recs,
                  "stats_window": (st0, st1),
                  "stats_stretch": tuple(tracer.snaps)}
    lost = sum(1 for r in recs if r.error ==
               "no answer within the grace period")
    out["check"] = lambda ref, cells: check_serve(
        recs, sched, pool, ref, cells, config, args.seed,
        out["check_params"]["sample_blocks"], lost)


def check_serve(recs, sched, pool, ref, cells, config, seed, n_blocks,
                lost):
    """Compare every answer whose pinned block is in a seeded sample of
    the blocks the window asked about (the unpinned one always)."""
    names = [k["name"] for k in config["knobs"]]
    sig = lambda q: tuple(sorted(q["overrides"].items()))
    asked = sorted({sig(sched.questions[r.index]) for r in recs if r.ok})
    rng = loads.rng_for(seed, 6)
    rest = [s for s in asked if s]
    pick = ([()] if () in asked else []) + [
        rest[i] for i in sorted(rng.choice(
            len(rest), min(len(rest), n_blocks - (() in asked)),
            replace=False))]
    t = time.perf_counter()
    base_c, base_e = ref.evaluate(np.ones((1, ref.K), np.float32))
    blocks = {}
    for s in pick:
        cols = sorted({c for r in recs if r.ok
                       and sig(sched.questions[r.index]) == s
                       for c in correct.resolve(
                           cells, sched.questions[r.index]["workload"],
                           sched.questions[r.index]["archs"])})
        cand = correct.pin(pool, names, dict(s))
        cyc, en = ref.evaluate(cand, cols)
        blocks[s] = {"cand": cand, "cycles": cyc, "energy": en,
                     "cost": ref.cost(cand), "base_c": base_c[0],
                     "base_e": base_e[0]}
    chosen = [r for r in recs if r.ok
              and sig(sched.questions[r.index]) in blocks]
    log(f"reference: {len(blocks)} of {len(asked)} asked blocks, "
        f"{len(chosen)} answers compared, in "
        f"{time.perf_counter() - t:.3f} s")
    return correct.serve_numbers(
        [r.answer for r in chosen],
        [sched.questions[r.index] for r in chosen],
        lambda ov: blocks[tuple(sorted(ov.items()))], cells, lost)


RUNNERS = {"sweep": run_sweep, "serve": run_serve}


# -- the run ----------------------------------------------------------------


def run(args, require_tpu: bool = True, root: Path = ROOT) -> int:
    cell, manifest, config, traffic, check = load_cell(args.workload, root)
    limits = check["limits"]
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} device_kind={d.device_kind} "
        f"count={len(devs)}")
    if require_tpu and (d.platform != "tpu" or len(devs) < cell["chips"]):
        log(f"bench: no result: the cell needs {cell['chips']} TPU chip(s), "
            f"JAX reports {len(devs)} {d.platform} device(s) (there is no "
            f"CPU fallback)")
        return 1
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clog = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(clog)

    from export import plain_cells
    from reference import Reference

    t = time.perf_counter()
    ex = build_explorer(config)
    clog.report(f"Explorer({config['explorer']}): build, condensation, "
                f"batch-1 baseline dispatch", time.perf_counter() - t,
                sys.stderr)
    check_config(ex, config)
    tracer = Tracer(bool(args.trace))
    out = {"check_params": check}
    try:
        RUNNERS[traffic["kind"]](ex, config, traffic, args, clog, tracer,
                                 out)
        log(f"setup_s: {out['setup_s']:.3f}")
        stats = d.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": cell["chips"], "memory_peak_bytes": peak}
        result = {"correct": False, "attempted": out["attempted"],
                  "failed": out["failed"]}
        if args.trace:
            summ = tracer.summary()
            run_data = dict(out["run"], trace=summ)
            device["busy_s"] = summ["busy_s"]
            device["window_s"] = summ["window_s"]
            metrics = {}
            for name in metric_names(manifest, args.workload, "per_layer"):
                v = read_metric(name, run_data, root / "bench")
                if v is not None:
                    unit = {m["name"]: m["unit"]
                            for m in manifest["per_layer"]}[name]
                    metrics[name] = {"value": float(v), "unit": unit}
            breakdown = {"device_ops": summ["device_ops"],
                         "idle_gaps": summ["idle_gaps"]}
            log(f"trace: {summ['window_s']:.3f} s traced, device busy "
                f"{summ['busy_s']:.3f} s, {summ['launches']} launches")
        else:
            vals = dict(out["e2e"], setup_s=out["setup_s"])
            units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
            metrics = {n: {"value": float(vals[n]), "unit": units[n]}
                       for n in metric_names(manifest, args.workload,
                                             "end_to_end")}
            breakdown = None
    finally:
        tracer.cleanup()

    # the reference runs on the host, after the window, the memory
    # reading and the service's shutdown
    ref = Reference(plain_cells(ex), config)
    t = time.perf_counter()
    pins = pin_numbers(ref, config)
    numbers = dict(out["check"](ref, ref.cells), **pins)
    log(f"comparison: {time.perf_counter() - t:.3f} s")
    result["correct"] = (correct.judge(numbers, limits)
                         and out["attempted"] > 0)
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": numbers.get(k), "limit": limits.get(k)}
                        for k in {**limits, **numbers}}
    for k, v in result["checks"].items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


def pin_numbers(ref, config) -> dict:
    """The reference's cycles and energy at the configuration file's
    pinned design points (theta = 1, each knob alone off 1, and a mix)
    against the values pinned there: the graphs, run lists, op classes
    and storage accesses that the reference reads from the program still
    make the configured model."""
    pins = config["pins"]
    c, e = ref.evaluate(np.asarray(pins["theta"], np.float32))
    return {"pin_err_max": float(max(
        correct.rel_err(c, pins["cycles"]).max(),
        correct.rel_err(e, pins["energy"]).max()))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
