"""Serving benchmark (ROADMAP item 1): DSE-as-a-service throughput.

Sections:

* ``serve/throughput`` — N concurrent clients firing a mixed query
  stream (full-matrix, arch-subset, override and top-k queries, each
  distinct question repeated) at one :class:`repro.serve.DSEService`;
  reports end-to-end queries/s plus how the micro-batcher coalesced the
  stream (windows, device dispatches, mean batch size) and the
  device-side configs/s actually evaluated.  The small-budget run also
  replays the same stream sequentially and asserts the threaded answers
  are identical — determinism under concurrency, measured live.
* ``serve/cache-hit`` — the same run's answer-cache counters
  (hits / misses / coalesced and the combined hit ratio).  The
  small-budget run asserts the ratio is > 0 (a repeated question must
  never reach the device twice).
* ``serve/surrogate`` — the staged oracle hierarchy's fast tier: the
  SAME cold distinct-query stream served twice, once by a service whose
  every query the trained surrogate answers and once by the packed-only
  service; reports per-fresh-query latency for both tiers, the speedup
  (asserted ≥ 10x on the small budget), the surrogate's training time,
  and the measured fallback rate at the default confidence threshold.
  The small-budget row is guarded against the recorded snapshot
  (``benchmarks.baseline``), so fast-tier throughput regressions fail CI.
* ``serve/degraded`` — graceful degradation throughput: the same cold
  distinct-query stream served while the circuit breaker is latched
  open (packed dispatch failing by fault plan, half-open probe out of
  reach), so every answer comes from the surrogate tier stamped
  ``tier="surrogate-degraded"`` with its widened bound.  The
  small-budget row is guarded against the recorded snapshot — a
  regression in degraded-mode throughput means the failure path got
  slower, exactly when it matters.
* ``serve/recovery`` — the full chaos arc measured end to end: a finite
  fault window (transient dispatch errors) trips the breaker mid-
  stream, queries degrade, then the shed -> half-open-probe walk is
  timed until the first exact ``tier="packed"`` answer comes back;
  reports breaker opens/sheds, degraded/failed counts, probe count and
  time-to-recovery.
* ``serve/sharded`` — ``PackedMatrix.evaluate(sharded=True)`` vs the
  single-device path on the same candidate batch: devices used, both
  throughputs, speedup, and bitwise agreement (always asserted).  A
  process that sees one device reports the row as "not measured"; run
  under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to
  exercise the path on a CPU host.  The > 2x speedup floor is asserted
  only when the host has >= 8 physical cores — forced host devices on
  fewer cores time-slice the same silicon, so the speedup is real
  parallelism there, not on a 1-core CI box.

Budget: ``BENCH_BUDGET=small`` shrinks the pool / stream (same code
paths); rows are recorded via ``python -m benchmarks.run --only serve
--json`` into ``BENCH_serve.json``.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np

SMALL = os.environ.get("BENCH_BUDGET", "").lower() == "small"


def _query_stream(ex) -> List:
    """A deterministic client workload derived from the served matrix:
    per-workload full-matrix / top-k / override queries plus per-arch
    subset queries — the distinct questions a cache-hit run repeats."""
    from repro.serve import Query

    workloads = sorted({cs.workload for cs in ex.compiled})
    archs = sorted({cs.arch for cs in ex.compiled})
    knob = ex.space.names[0]
    qs = []
    for w in workloads:
        qs.append(Query.make(workload=w))
        qs.append(Query.make(workload=w, top_k=3))
        qs.append(Query.make(workload=w, overrides={knob: 2.0}))
    for a in archs:
        qs.append(Query.make(archs=[a]))
    return qs


def _bench_service(rows: List[Dict]) -> None:
    from repro.core.aidg.explorer import Explorer
    from repro.serve import DSEService

    ex = Explorer()                    # packed engine, operator matrix
    pool = 32 if SMALL else 128
    reps = 3 if SMALL else 8
    distinct = _query_stream(ex)
    stream = distinct * reps
    # chunk=pool pads every stacked window to ONE compiled batch shape,
    # so variable window composition never re-traces mid-run
    kw = dict(pool=pool, chunk=pool, max_batch=8, window_s=0.005)

    # warm pass: compiles the fixed-shape dispatch + scenario kernels
    with DSEService(ex, **kw) as warm:
        warm.query_many(distinct)

    svc = DSEService(ex, **kw)         # fresh answer cache, warm jit cache
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=8) as tp:
        answers = list(tp.map(svc.query, stream))
    dt = time.perf_counter() - t0
    st = svc.stats()
    svc.close()

    n = len(stream)
    cs = st["cache"]
    configs = st["dispatched_candidates"] * st["cells"]
    rows.append({"name": "serve/throughput", "us_per_call": dt / n * 1e6,
                 "derived": (f"clients=8;queries={n};"
                             f"distinct={len(distinct)};pool={pool};"
                             f"cells={st['cells']};"
                             f"queries_per_s={n / dt:.0f};"
                             f"windows={st['windows']};"
                             f"device_dispatches={st['device_dispatches']};"
                             f"mean_batch={st['mean_batch']:.2f};"
                             f"configs_per_s={configs / dt:.0f}")})
    rows.append({"name": "serve/cache-hit", "us_per_call": dt / n * 1e6,
                 "derived": (f"hits={cs['hits']};misses={cs['misses']};"
                             f"coalesced={cs['coalesced']};"
                             f"hit_ratio={st['hit_ratio']:.3f}")})
    if SMALL and st["hit_ratio"] <= 0.0:
        raise AssertionError(
            f"answer cache never hit over {n} queries "
            f"({len(distinct)} distinct): {cs}")
    if cs["hits"] + cs["coalesced"] + cs["misses"] != n:
        raise AssertionError(f"cache counters {cs} do not account for "
                             f"all {n} queries")

    if SMALL:
        # determinism under concurrency, asserted live: the threaded
        # answers must equal a sequential replay of the same stream
        with DSEService(ex, **kw) as ref_svc:
            ref = ref_svc.query_many(stream)
        if answers != ref:
            bad = [i for i, (a, b) in enumerate(zip(answers, ref))
                   if a != b]
            raise AssertionError(
                f"threaded answers diverge from sequential replay at "
                f"stream positions {bad[:5]}")


# -- the staged oracle hierarchy's fast tier ---------------------------------

def _bench_surrogate(rows: List[Dict]):
    """Benches the fast tier; returns ``(explorer, bundle)`` so the
    fault-path benches reuse the trained surrogate instead of paying for
    training twice."""
    from repro.core.aidg.explorer import Explorer
    from repro.serve import DSEService
    from repro.surrogate import SurrogateConfig, train_surrogate

    ex = Explorer()                    # packed engine, operator matrix
    cfg = SurrogateConfig(n_samples=96 if SMALL else 192,
                          steps=600 if SMALL else 1500)
    t0 = time.perf_counter()
    bundle = train_surrogate(ex, cfg)
    t_train = time.perf_counter() - t0

    pool = 32 if SMALL else 128
    kw = dict(pool=pool, chunk=pool, max_batch=8)
    distinct = _query_stream(ex)
    n = len(distinct)

    # warm both tiers' compiled shapes, then time COLD sequential streams
    # on fresh services: every query is a miss, so the per-query cost is
    # the tier's evaluation itself, not the answer cache
    with DSEService(ex, surrogate=bundle, surrogate_max_err=np.inf,
                    **kw) as warm:
        warm.query_many(distinct)
    with DSEService(ex, **kw) as warm:
        warm.query_many(distinct)

    def cold_run(**extra):
        svc = DSEService(ex, **kw, **extra)
        t0 = time.perf_counter()
        answers = svc.query_many(distinct)
        dt = time.perf_counter() - t0
        st = svc.stats()
        svc.close()
        return dt, st, answers

    t_sur, st_sur, a_sur = cold_run(surrogate=bundle,
                                    surrogate_max_err=np.inf)
    t_pkd, st_pkd, _ = cold_run()
    if st_sur["tiers"]["surrogate"] != n or st_pkd["tiers"]["packed"] != n:
        raise AssertionError(
            f"tier routing broke the cold streams: {st_sur['tiers']} / "
            f"{st_pkd['tiers']} for {n} distinct queries")

    # the honest fallback rate at the DEFAULT confidence threshold
    _, st_def, _ = cold_run(surrogate=bundle)
    speedup = t_pkd / t_sur
    configs = n * pool * st_sur["cells"]
    rows.append({"name": "serve/surrogate", "us_per_call": t_sur / n * 1e6,
                 "derived": (f"queries={n};pool={pool};"
                             f"cells={st_sur['cells']};"
                             f"sur_us_per_query={t_sur / n * 1e6:.0f};"
                             f"packed_us_per_query={t_pkd / n * 1e6:.0f};"
                             f"speedup={speedup:.1f}x;"
                             f"configs_per_s={configs / t_sur:.0f};"
                             f"train_s={t_train:.1f};"
                             f"fallback_rate={st_def['fallback_rate']:.2f};"
                             f"max_err={st_def['surrogate_max_err']}")})
    if SMALL and speedup < 10.0:
        raise AssertionError(
            f"surrogate tier speedup {speedup:.1f}x < 10x over the packed "
            f"dispatch ({t_sur / n * 1e6:.0f}us vs {t_pkd / n * 1e6:.0f}us "
            f"per query)")
    if SMALL:
        for a in a_sur:
            if a.tier != "surrogate" or a.err_bound <= 0.0:
                raise AssertionError(
                    f"cold surrogate stream produced a {a.tier!r} answer "
                    f"(err_bound={a.err_bound})")
    return ex, bundle


# -- the failure path: degraded throughput + chaos recovery ------------------

def _bench_faults(rows: List[Dict], ex, bundle) -> None:
    from repro.serve import (CircuitBreaker, DEGRADED_WIDEN, DSEService,
                             Query, RetryPolicy, ServeError)

    pool = 32 if SMALL else 128
    kw = dict(pool=pool, chunk=pool, max_batch=8,
              surrogate=bundle, surrogate_max_err=-1.0,  # packed routing
              degraded_max_err=np.inf)
    distinct = _query_stream(ex)
    n = len(distinct)

    # -- serve/degraded: breaker latched open, every cold query answered
    # by the surrogate with its widened bound
    def latched():
        return DSEService(
            ex, **kw, retry=RetryPolicy(max_attempts=1, base_s=0.0),
            breaker=CircuitBreaker(open_after=1, probe_after=10 ** 9),
            fault_plan="packed[0]=error")

    with latched() as warm:               # compile the surrogate shapes
        warm.query_many(distinct, return_exceptions=True)
    svc = latched()
    t0 = time.perf_counter()
    answers = svc.query_many(distinct)
    t_deg = time.perf_counter() - t0
    st = svc.stats()
    svc.close()
    if SMALL:
        for a in answers:
            if a.tier != "surrogate-degraded" or a.err_bound <= 0.0:
                raise AssertionError(
                    f"latched-breaker stream produced a {a.tier!r} answer "
                    f"(err_bound={a.err_bound})")
        if st["tiers"]["surrogate-degraded"] != n:
            raise AssertionError(
                f"degraded tier accounted {st['tiers']} for {n} queries")
    configs = n * pool * st["cells"]
    rows.append({"name": "serve/degraded", "us_per_call": t_deg / n * 1e6,
                 "derived": (f"queries={n};pool={pool};"
                             f"cells={st['cells']};"
                             f"deg_us_per_query={t_deg / n * 1e6:.0f};"
                             f"configs_per_s={configs / t_deg:.0f};"
                             f"widen={DEGRADED_WIDEN};"
                             f"breaker={st['breaker']['state']};"
                             f"breaker_shed={st['breaker']['shed']}")})

    # -- serve/recovery: a finite fault window trips the breaker, then
    # the shed -> probe walk is timed until packed answers return
    plan = "packed[0:3]=error"
    svc = DSEService(ex, **kw,
                     retry=RetryPolicy(max_attempts=1, base_s=0.0),
                     breaker=CircuitBreaker(open_after=1, probe_after=1),
                     fault_plan=plan)
    t0 = time.perf_counter()
    outcomes = svc.query_many(distinct, return_exceptions=True)
    probe = Query.make(workload=distinct[0].workload, top_k=17)
    probes, recovered = 0, None
    for _ in range(16):
        # rejected opportunities come back as DEGRADED answers here (the
        # surrogate covers everything), so walk until the first exact one
        probes += 1
        try:
            out = svc.query_many([probe])[0]
        except ServeError:
            continue
        if out.tier == "packed":
            recovered = out
            break
    t_rec = time.perf_counter() - t0
    st = svc.stats()
    svc.close()
    if len(outcomes) != n:
        raise AssertionError(
            f"{n} queries submitted under chaos, {len(outcomes)} resolved")
    if recovered is None or recovered.tier != "packed":
        raise AssertionError(
            f"breaker never recovered to the packed tier under {plan!r} "
            f"(state {st['breaker']['state']})")
    if SMALL and st["breaker"]["opens"] < 1:
        raise AssertionError(f"fault window {plan!r} never tripped the "
                             f"breaker")
    degraded = sum(1 for o in outcomes
                   if not isinstance(o, BaseException)
                   and o.tier == "surrogate-degraded")
    rows.append({"name": "serve/recovery",
                 "us_per_call": t_rec / (n + probes) * 1e6,
                 "derived": (f"queries={n};plan={plan.replace(';', '|')};"
                             f"degraded={degraded};"
                             f"opens={st['breaker']['opens']};"
                             f"breaker_shed={st['breaker']['shed']};"
                             f"probes={probes};"
                             f"retries={st['retries']};"
                             f"recovered_tier={recovered.tier};"
                             f"recovery_ms={t_rec * 1e3:.1f}")})


# -- sharded probe ----------------------------------------------------------

def _bench_sharded(rows: List[Dict]) -> None:
    """Single-device vs candidate-sharded PackedMatrix throughput over the
    devices THIS process sees.  With one device there is nothing to shard
    and the row says "not measured": starting a child process to force
    host devices would need the device the parent already holds (run the
    whole bench under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    to exercise the path on a CPU host)."""
    import jax

    from repro.core.aidg.explorer import Explorer, random_candidates

    D = jax.local_device_count()
    if D == 1:
        rows.append({"name": "serve/sharded", "us_per_call": 0.0,
                     "derived": "devices=1;not measured"})
        return
    ex = Explorer()
    pm = ex.packed_matrix()
    B = -(-(64 if SMALL else 512) // D) * D
    cand = random_candidates(ex.space, B, seed=0)

    def best_of(fn, reps=3):
        fn()                           # warm-up / compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t_single = best_of(lambda: pm.evaluate(cand))
    t_shard = best_of(lambda: pm.evaluate(cand, sharded=True))
    exact = bool(np.array_equal(pm.evaluate(cand),
                                pm.evaluate(cand, sharded=True)))
    configs = B * pm.n_cells
    speedup = t_single / t_shard
    rows.append({"name": "serve/sharded", "us_per_call": 0.0,
                 "derived": (f"devices={D};batch={B};cells={pm.n_cells};"
                             f"single_configs_per_s="
                             f"{configs / t_single:.0f};"
                             f"sharded_configs_per_s="
                             f"{configs / t_shard:.0f};"
                             f"speedup={speedup:.2f}x;"
                             f"exact={exact};"
                             f"host_cores={os.cpu_count()}")})
    if not exact:
        raise AssertionError(
            "sharded evaluation is not bitwise-equal to single-device")
    cores = os.cpu_count() or 1
    if cores >= 8 and speedup < 2.0:
        # forced host devices only parallelize when cores back them; on
        # a >= 8-core host a sub-2x sharded path is a real regression
        raise AssertionError(
            f"sharded speedup {speedup:.2f}x < 2x on {D} devices / "
            f"{cores} cores")


def run(rows: List[Dict]) -> None:
    _bench_service(rows)
    ex, bundle = _bench_surrogate(rows)
    _bench_faults(rows, ex, bundle)
    _bench_sharded(rows)
    from .baseline import assert_baseline, guard_enabled
    if guard_enabled():
        assert_baseline(rows, section="serve",
                        names=("serve/surrogate", "serve/degraded"))


if __name__ == "__main__":
    rows: List[Dict] = []
    run(rows)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
