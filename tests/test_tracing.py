"""The program's own tracing: host spans (``repro.tracing``) inside
``Explorer.explore``, the packed dispatch and the serve path, the named
scope of each shape bucket in the compiled packed evaluator, the bucket
statistics a trace's scopes are read against, and the service's queue-wait
and window counters.  All on the CPU, over the ten-cell operator matrix."""

from __future__ import annotations

import glob
import re
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.aidg.dse import PackedMatrix
from repro.core.aidg.explorer import Explorer, random_candidates
from repro.serve import DSEService, Query, batcher
from repro.tracing import PREFIX

BATCH = 64          # explore batch = service pool: one compiled shape


@pytest.fixture(scope="module")
def ex():
    return Explorer()


def _trace(fn, logdir):
    """Run ``fn`` under a profiler session; its result and the program's
    host spans ``(name without prefix, start_ns, end_ns, args)``."""
    with jax.profiler.trace(str(logdir)):
        out = fn()
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    spans = [(e.name[len(PREFIX):], e.start_ns, e.end_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(PREFIX)]
    return out, spans


def _inside(spans, child, parent):
    """Every ``child`` span lies inside some ``parent`` span."""
    kids = [s for s in spans if s[0] == child]
    outer = [s for s in spans if s[0] == parent]
    return kids and all(any(p[1] <= k[1] and k[2] <= p[2] for p in outer)
                        for k in kids)


@pytest.fixture(scope="module")
def traced_explore(ex, tmp_path_factory):
    cand = random_candidates(ex.space, BATCH, seed=3)
    plain = ex.explore(cand)
    traced, spans = _trace(lambda: ex.explore(cand),
                           tmp_path_factory.mktemp("explore_trace"))
    return plain, traced, spans


def test_explore_is_bitwise_equal_with_a_profiler_session(traced_explore):
    plain, traced, _ = traced_explore
    for name in ("cycles", "latency", "energy", "cost", "pareto"):
        a, b = getattr(plain, name), getattr(traced, name)
        assert a.dtype == b.dtype and np.array_equal(a, b), name


@pytest.mark.parametrize("child,parent", [
    ("explore.evaluate", "explore.call"),
    ("explore.score", "explore.call"),
    ("explore.pareto", "explore.call"),
    ("packed.dispatch", "explore.evaluate"),
    ("packed.wait", "explore.evaluate"),
    ("packed.fetch", "explore.evaluate"),
])
def test_a_traced_explore_call_has_each_span_nested(traced_explore, child,
                                                    parent):
    _, _, spans = traced_explore
    assert [s[0] for s in spans].count("explore.call") == 1
    assert _inside(spans, child, parent)


def test_explore_spans_run_in_order(traced_explore):
    _, _, spans = traced_explore
    start = {n: s for n, s, _, _ in spans}
    assert (start["explore.evaluate"] < start["explore.score"]
            < start["explore.pareto"])
    assert (start["packed.dispatch"] < start["packed.wait"]
            < start["packed.fetch"])


def test_the_pareto_span_carries_the_rows_it_ranks(traced_explore):
    _, _, spans = traced_explore
    (args,) = [a for n, _, _, a in spans if n == "explore.pareto"]
    assert int(args["rows"]) == BATCH


def test_compiled_evaluator_names_each_bucket_and_the_composition(ex):
    pm = ex.packed_matrix()
    n_buckets = pm.stats()["buckets"]
    hlo = pm._full_fn().lower(
        np.ones((BATCH, ex.space.n), np.float32)).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    scopes = {m for o in op_names for m in re.findall(
        r"packed\.(bucket\d\d|compose)\b", o)}
    assert scopes == ({f"bucket{i:02d}" for i in range(n_buckets)}
                      | {"compose"})
    # op_scopes maps the instructions of the evaluator last dispatched,
    # this batch size, to the same scopes
    ex.explore(random_candidates(ex.space, BATCH, seed=4))
    assert {s.split(".", 1)[1] for s in pm.op_scopes().values()} == scopes
    assert pm.stats()["op_scopes"] == pm.op_scopes()


def _row_cost(pm, i):
    """The bucketing cost model, restated: one row's unpadded work."""
    c = pm.rows[i].cond
    q = max((len(nd) for nd, _, _, sl, _ in pm.rows[i].queues if sl > 1),
            default=0)
    return (max(1, c.schedule.n_levels) * max(1, c.schedule.width)
            * max(1, c.preds_lv.shape[1]) + pm.n_iters * q * 8)


def test_bucket_detail_partitions_the_rows_and_prices_the_padding(ex):
    pm = ex.packed_matrix()
    st = pm.stats()
    detail = st["bucket_detail"]
    buckets = pm._bucketize()
    assert len(detail) == st["buckets"] == len(buckets)
    assert sum(d["rows"] for d in detail) == pm.n_rows
    assert sum(d["LV"] for d in detail) == st["scan_len"]
    assert set().union(*(d["cells"] for d in detail)) == set(
        range(pm.n_cells))
    for d, b in zip(detail, buckets):
        conds = [pm.rows[i].cond for i in b]
        assert d["LV"] == max(c.schedule.n_levels for c in conds)
        assert d["W"] == max(c.schedule.width for c in conds)
        assert d["rcost"] == sum(_row_cost(pm, i) for i in b)
        assert d["rcost"] <= d["bcost"]
    eff = (sum(_row_cost(pm, i) for i in range(pm.n_rows))
           / sum(d["bcost"] for d in detail))
    assert 0.0 < st["pad_efficiency"] <= 1.0
    assert st["pad_efficiency"] == pytest.approx(eff, rel=1e-12)


def test_a_one_row_matrix_wastes_no_padding(ex):
    cs = ex.compiled[0]
    spec = cs.pack_spec(cs.projection(ex.space), n_knobs=ex.space.n)
    st = PackedMatrix.build([spec], ex.space.n).stats()
    assert st["buckets"] == 1 and st["bucket_detail"][0]["rows"] == 1
    assert st["pad_efficiency"] == 1.0


# -- serve path -----------------------------------------------------------


def _service(ex, **kw):
    pool = random_candidates(ex.space, BATCH, seed=5)
    return DSEService(ex, candidates=pool, **kw)


def test_queue_wait_counts_one_wait_per_dispatched_query(ex):
    svc = _service(ex, max_batch=4, window_s=0.005)
    try:
        with svc.batcher.hold():
            futs = [svc.submit(workload="gemm", top_k=k) for k in (1, 2, 3)]
            held = 0.05
            time.sleep(held)
        for f in futs:
            f.result(timeout=120.0)
        svc.query(workload="attention", timeout=120.0)
        svc.batcher.drain()
        st = svc.stats()
        assert st["queue_waited"] == st["dispatched_queries"] == 4
        assert st["windows"] == len(svc.batcher.dispatch_log) == 2
        # the held window's three queries each waited out the hold
        assert st["queue_wait_max_s"] >= held
        assert st["queue_wait_s"] >= 3 * held
        assert st["queue_wait_max_s"] <= st["queue_wait_s"]
        # the replay path bypasses the batcher: a window, no queue wait
        svc.query_many([Query.make(workload="gemm")])
        st2 = svc.stats()
        assert st2["dispatched_queries"] == 5 and st2["windows"] == 3
        assert st2["queue_waited"] == 4
    finally:
        svc.close()


def test_window_counters_outlive_the_capped_logs(ex, monkeypatch):
    monkeypatch.setattr(batcher, "LOG_CAP", 3)
    svc = _service(ex)
    try:
        qs = [Query.make(workload="gemm", top_k=k) for k in range(1, 6)]
        for q in qs:
            svc.query_many([q])
        st = svc.stats()
        assert st["windows"] == st["dispatched_queries"] == 5
        assert st["device_dispatches"] == 5
        assert [w for w in svc.window_log] == [[q.key] for q in qs[-3:]]
        assert len(svc.evaluated_log) == 3
    finally:
        svc.close()


def test_a_traced_query_has_its_window_tier_and_rank_spans(ex, tmp_path):
    svc = _service(ex)
    try:
        svc.query(workload="gemm")                     # window 0, untraced
        _, spans = _trace(lambda: svc.query(workload="attention"), tmp_path)
    finally:
        svc.close()
    (window,) = [s for s in spans if s[0] == "serve.window"]
    assert window[3] == {"window": 1}
    assert _inside(spans, "serve.exact_tier", "serve.window")
    assert _inside(spans, "serve.rank", "serve.exact_tier")
    assert _inside(spans, "packed.wait", "serve.exact_tier")
