"""DSE-as-a-service load harness: many concurrent clients asking "which
accelerator + config for my workload?" against ONE shared
:class:`repro.serve.DSEService` — the ROADMAP's serving story, end to
end.

Fires ``--clients`` threads over a mixed query stream (full-matrix,
arch-subset, knob-override and top-k queries, each distinct question
asked ``--repeats`` times), prints the served recommendations and the
service counters, then exits non-zero unless

* the answer cache actually hit (hit ratio > 0 — repeated questions
  must never reach the device twice), and
* the device-sharded evaluator agrees bitwise with the single-device
  path on the service's candidate pool.

This is the CI ``serve-smoke`` gate; force a multi-device host CPU with::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python examples/serve_dse.py --budget small

``--faults`` switches to the chaos harness (the CI ``chaos-smoke``
gate): the same concurrent load is fired at a service whose packed
dispatch is being killed by a deterministic fault plan
(``SERVE_FAULT_PLAN`` or a built-in window of transient errors, a
poisoned payload, and a worker kill), with the surrogate tier armed for
degradation.  It exits non-zero unless

* zero queries are lost or duplicated — every submission resolves to
  exactly one outcome: an answer to its own question or a structured
  error,
* the circuit breaker actually opened under the faults AND recovered —
  the post-chaos service answers ``tier="packed"`` again, and
* every ``surrogate-degraded`` answer is within its stated widened
  error bound of the packed oracle recomputed offline.
"""

import argparse
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.aidg.explorer import Explorer
from repro.serve import (CircuitBreaker, DSEService, FaultPlan, Query,
                         RetryPolicy, ServeError, WorkerKill)
from repro.serve.faults import ENV_FAULT_PLAN

# the built-in chaos window: retries absorb the first error, the second
# dispatch exhausts its budget (breaker trips), then a poisoned payload
# and a worker-thread kill keep the oracle dead before the plan runs dry
# and the half-open probe recovers
DEFAULT_FAULT_PLAN = ("packed[0:4]=error;packed[4]=poison;"
                      "packed[5]=kill;packed[6:8]=error")


def build_stream(ex, repeats):
    """The client workload: every served workload asked three ways
    (full matrix, top-3, with a pinned knob), every arch asked for its
    own profile — repeated so the cache has something to hit."""
    workloads = sorted({cs.workload for cs in ex.compiled})
    archs = sorted({cs.arch for cs in ex.compiled})
    knob = ex.space.names[0]
    distinct = []
    for w in workloads:
        distinct += [Query.make(workload=w),
                     Query.make(workload=w, top_k=3),
                     Query.make(workload=w, overrides={knob: 2.0})]
    distinct += [Query.make(archs=[a]) for a in archs]
    return distinct, distinct * repeats


def run_faults(args):
    """The chaos harness (CI ``chaos-smoke``): concurrent load against a
    fault-injected service, then the three gates from the module
    docstring — zero lost queries, breaker opened AND recovered,
    degraded answers honest about their widened bounds."""
    spec = os.environ.get(ENV_FAULT_PLAN) or DEFAULT_FAULT_PLAN
    plan = FaultPlan.parse(spec)
    if plan.max_faulty_attempt() < 0:
        print(f"FAIL: fault plan {spec!r} never ends — the breaker could "
              f"not recover", file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    ex = Explorer()
    print(f"compiled matrix: {len(ex.compiled)} cells, "
          f"{ex.space.n} knobs ({time.perf_counter() - t0:.1f}s)")

    from repro.surrogate import SurrogateConfig, train_surrogate
    t0 = time.perf_counter()
    bundle = train_surrogate(ex, SurrogateConfig(
        n_samples=64 if args.budget == "small" else 128,
        steps=400 if args.budget == "small" else 1000))
    # cover roughly the better-calibrated half of the matrix, so the
    # chaos run exercises BOTH rungs of the degradation ladder: covered
    # queries degrade, uncovered ones fail fast
    degraded_max_err = float(np.median(bundle.err_bound))
    print(f"surrogate trained in {time.perf_counter() - t0:.1f}s; "
          f"degraded coverage bound {degraded_max_err:.3f} "
          f"({int(np.sum(bundle.err_bound <= degraded_max_err))}/"
          f"{len(bundle.err_bound)} cells)")

    pool = 32 if args.budget == "small" else 128
    repeats = args.repeats or (3 if args.budget == "small" else 8)
    distinct, stream = build_stream(ex, repeats)
    print(f"fault plan: {plan.to_spec()}")

    svc = DSEService(ex, pool=pool, chunk=pool, max_batch=8,
                     window_s=0.005, surrogate=bundle,
                     surrogate_max_err=-1.0,     # packed unless degraded
                     retry=RetryPolicy(max_attempts=2, base_s=0.001),
                     breaker=CircuitBreaker(open_after=1, probe_after=1),
                     fault_plan=plan, degraded_max_err=degraded_max_err)
    ok = True
    try:
        def ask(q):
            try:
                return svc.query(q, timeout=120.0)
            except ServeError as e:
                return e
            except WorkerKill as e:
                return e

        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=args.clients) as tp:
            outcomes = list(tp.map(ask, stream))
        dt = time.perf_counter() - t0

        st = svc.stats()
        answers = [o for o in outcomes if not isinstance(o, BaseException)]
        errors = [o for o in outcomes if isinstance(o, BaseException)]
        by_tier = {}
        for a in answers:
            by_tier[a.tier] = by_tier.get(a.tier, 0) + 1
        print(f"\n{len(stream)} queries from {args.clients} clients in "
              f"{dt:.2f}s under chaos: {len(answers)} answered "
              f"{by_tier}, {len(errors)} failed structurally "
              f"({sorted(set(type(e).__name__ for e in errors))}); "
              f"retries={st['retries']} worker_restarts="
              f"{st['worker_restarts']} breaker={st['breaker']['state']} "
              f"opens={st['breaker']['opens']}")

        # gate 1: zero lost / duplicated queries, each answer its own
        if len(outcomes) != len(stream):
            print(f"FAIL: {len(stream)} submitted, {len(outcomes)} "
                  f"resolved", file=sys.stderr)
            ok = False
        mismatched = sum(1 for q, o in zip(stream, outcomes)
                         if not isinstance(o, BaseException)
                         and o.query != q)
        if mismatched:
            print(f"FAIL: {mismatched} answers do not match their own "
                  f"query (reorder/swap)", file=sys.stderr)
            ok = False

        # gate 2: the breaker opened under the faults AND recovers —
        # walk the shed->probe cycle until a packed answer comes back
        if st["breaker"]["opens"] < 1:
            print("FAIL: the fault plan never tripped the circuit "
                  "breaker", file=sys.stderr)
            ok = False
        probe = Query.make(workload=distinct[0].workload, top_k=17)
        recovered = None
        for _ in range(2 * plan.max_faulty_attempt() + 4):
            try:
                recovered = svc.query(probe, timeout=120.0)
                break
            except (ServeError, WorkerKill):
                continue
        if recovered is None or recovered.tier != "packed":
            print(f"FAIL: breaker never recovered to the packed tier "
                  f"(last state {svc.breaker.state})", file=sys.stderr)
            ok = False
        else:
            print(f"breaker recovered: {svc.breaker.transitions} -> "
                  f"tier={recovered.tier}")

        # gate 3: degraded answers honest within their widened bounds,
        # against the packed oracle recomputed offline (no faults)
        degraded = {a.query.key: a for a in answers
                    if a.tier == "surrogate-degraded"}
        if degraded:
            # fault_plan="" explicitly DISARMS injection for the oracle
            # service — without it the SERVE_FAULT_PLAN env hook would
            # poison the recompute too
            with DSEService(ex, pool=pool, chunk=pool,
                            max_batch=8, fault_plan="") as clean:
                exact = clean.query_many([a.query
                                          for a in degraded.values()])
            worst = 0.0
            for a, e in zip(degraded.values(), exact):
                pool_lat = {d.theta: d.latency for d in e.designs}
                for d in a.designs:
                    if d.theta not in pool_lat:
                        continue        # tiers may rank different rows
                    rel = abs(d.latency - pool_lat[d.theta]) \
                        / pool_lat[d.theta]
                    worst = max(worst, rel / a.err_bound)
                    if rel > a.err_bound:
                        print(f"FAIL: degraded answer for "
                              f"{a.query.workload!r} off by {rel:.3f} "
                              f"> stated bound {a.err_bound:.3f}",
                              file=sys.stderr)
                        ok = False
            print(f"{len(degraded)} distinct degraded answers checked "
                  f"against the offline packed oracle (worst error at "
                  f"{worst:.2f} of the stated bound)")
        elif st["tiers"]["surrogate-degraded"] == 0:
            print("FAIL: chaos run produced no degraded answers — the "
                  "plan never exercised the degradation ladder",
                  file=sys.stderr)
            ok = False
    finally:
        svc.close()

    if not ok:
        return 1
    print("chaos-smoke gates passed")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--budget", choices=("small", "full"),
                    default=os.environ.get("BENCH_BUDGET", "small")
                    or "small")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=None,
                    help="times each distinct query is asked "
                         "(default: 3 small / 8 full)")
    ap.add_argument("--faults", action="store_true",
                    help="run the chaos harness: inject the "
                         f"${ENV_FAULT_PLAN} fault plan (or the built-in "
                         "default) and assert the failure-semantics gates")
    args = ap.parse_args(argv)
    if args.faults:
        return run_faults(args)
    pool = 32 if args.budget == "small" else 128
    repeats = args.repeats or (3 if args.budget == "small" else 8)

    t0 = time.perf_counter()
    ex = Explorer()
    print(f"compiled matrix: {len(ex.compiled)} cells, "
          f"{ex.space.n} knobs ({time.perf_counter() - t0:.1f}s)")
    distinct, stream = build_stream(ex, repeats)

    with DSEService(ex, pool=pool, chunk=pool, max_batch=8,
                    window_s=0.005) as svc:
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=args.clients) as tp:
            answers = list(tp.map(svc.query, stream))
        dt = time.perf_counter() - t0
        st = svc.stats()

    print(f"\n{len(stream)} queries from {args.clients} clients in "
          f"{dt:.2f}s ({len(stream) / dt:.0f} q/s), coalesced into "
          f"{st['windows']} windows / {st['device_dispatches']} device "
          f"dispatches (mean batch {st['mean_batch']:.1f})")

    print("\nserved recommendations (one per distinct question):")
    seen = set()
    for a in answers:
        if a.query.key in seen:
            continue
        seen.add(a.query.key)
        d = a.best
        what = a.query.workload or f"archs={list(a.query.archs)}"
        pins = ",".join(f"{k}={v:g}" for k, v in a.query.overrides)
        print(f"  {what:14s} {'[' + pins + ']' if pins else '':14s}"
              f"-> {a.best_arch:10s} latency {d.latency:.3f} "
              f"cost {d.cost:.2f} ({len(a.designs)} Pareto designs over "
              f"{len(a.cells)} cells)")

    cs = st["cache"]
    print(f"\ncache: {cs['hits']} hits + {cs['coalesced']} coalesced / "
          f"{cs['misses']} misses (hit ratio {st['hit_ratio']:.2f}); "
          f"{st['dispatched_candidates']} candidate rows evaluated "
          f"device-side")

    # -- the two serve-smoke gates -----------------------------------------
    ok = True
    if st["hit_ratio"] <= 0.0:
        print("FAIL: answer cache never hit", file=sys.stderr)
        ok = False

    pm = ex.packed_matrix()
    devices = pm.n_shards(None)
    cand = svc.pool
    single = pm.evaluate(cand)
    shard = pm.evaluate(cand, sharded=True)
    exact = bool(np.array_equal(single, shard))
    print(f"sharded check: {devices} device(s), "
          f"bitwise agreement = {exact}")
    if not exact:
        print("FAIL: sharded evaluation diverges from single-device",
              file=sys.stderr)
        ok = False

    if not ok:
        return 1
    print("serve-smoke gates passed")
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
