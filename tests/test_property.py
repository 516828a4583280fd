"""Hypothesis property tests on system invariants.

Skipped cleanly when ``hypothesis`` is not installed (it is a dev-only
dependency — see pyproject.toml ``[project.optional-dependencies] dev``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.acadl.storage import SetAssociativeCache
from repro.core.aidg import build_aidg, longest_path
from repro.core.aidg.dse import evaluate_theta, evaluate_theta_soft, sweep
from repro.core.aidg.explorer import (compile_scenario, default_scenarios,
                                      pareto_front)
from repro.core.acadl.sim import build_trace
from repro.core.archs import make_gamma_ag
from repro.core.mapping.gemm import gamma_gemm, init_gemm_memory
from repro.kernels import ops, ref
from repro.models.layers import apply_rope, chunked_attention, dense_attention

SETTINGS = dict(max_examples=25, deadline=None)


@given(st.integers(2, 24), st.integers(2, 24), st.integers(2, 24),
       st.integers(0, 5))
@settings(**SETTINGS)
def test_maxplus_matches_ref_any_shape(m, k, n, seed):
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    out = ops.maxplus_matmul(A, B, bm=8, bk=8, bn=8, interpret=True)
    np.testing.assert_allclose(out, ref.maxplus_matmul_ref(A, B), atol=1e-5)


@given(st.integers(1, 6), st.integers(1, 6), st.integers(1, 6),
       st.integers(0, 3))
@settings(**SETTINGS)
def test_gemm_kernel_matches_ref_any_shape(mq, kq, nq, seed):
    m, k, n = 8 * mq, 8 * kq, 8 * nq
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    out = ops.gemm(A, B, bm=16, bk=16, bn=16, interpret=True)
    np.testing.assert_allclose(out, ref.gemm_ref(A, B), atol=1e-4, rtol=1e-4)


@given(st.integers(0, 1000), st.integers(8, 64))
@settings(**SETTINGS)
def test_rope_preserves_norm(pos, dim):
    dim = (dim // 2) * 2
    x = jnp.asarray(np.random.default_rng(pos).normal(size=(1, 1, 1, dim)),
                    jnp.float32)
    y = apply_rope(x, jnp.asarray([[pos]]), 10_000.0)
    np.testing.assert_allclose(float(jnp.linalg.norm(x)),
                               float(jnp.linalg.norm(y)), rtol=1e-5)


@given(st.integers(0, 4))
@settings(max_examples=8, deadline=None)
def test_attention_causality(seed):
    """Perturbing future tokens never changes past outputs."""
    rng = np.random.default_rng(seed)
    s, h, d = 12, 2, 8
    q = jnp.asarray(rng.normal(size=(1, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, s, h, d)), jnp.float32)
    out = dense_attention(q, k, v, causal=True)
    k2 = k.at[:, -1].add(100.0)
    v2 = v.at[:, -1].add(-50.0)
    out2 = dense_attention(q, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(out[:, :-1]),
                               np.asarray(out2[:, :-1]), atol=1e-5)


@given(st.integers(0, 4))
@settings(max_examples=6, deadline=None)
def test_chunked_equals_dense_attention(seed):
    rng = np.random.default_rng(seed)
    s, h, d = 16, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(1, s, h, d)), jnp.float32)
               for _ in range(3))
    a = dense_attention(q, k, v, causal=True)
    b = chunked_attention(q, k, v, causal=True, chunk=4)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@given(st.floats(1.0, 3.0), st.floats(1.0, 3.0))
@settings(max_examples=10, deadline=None)
def test_aidg_monotone_in_work(s1, s2):
    """Scaling any latency up never reduces the estimated makespan."""
    ag, _ = make_gamma_ag(n_units=2)
    A = np.ones((16, 16), np.float32)
    init_gemm_memory(ag, A, A, memory="dram0", tile=8)
    units = (("lsu0", "matMulFu0", "vrf0"), ("lsu1", "matMulFu1", "vrf1"))
    prog = gamma_gemm(16, 16, 16, tile=8, units=units)
    trace = build_trace(ag, prog)
    aidg = build_aidg(ag, trace)
    t1 = longest_path(aidg, work=aidg.work * np.float32(s1)).max()
    t2 = longest_path(aidg, work=aidg.work * np.float32(max(s1, s2))).max()
    assert t2 >= t1 - 1e-6


# ---------------------------------------------------------------------------
# chain condensation ≡ uncondensed wavefront (repro.core.aidg.builder)
# ---------------------------------------------------------------------------

_DEFAULT_SCENARIOS = default_scenarios()
_SCN_IDS = [s.name for s in _DEFAULT_SCENARIOS]


def _theta_draw(prob, seed):
    rng = np.random.default_rng(seed)
    to = np.exp(rng.uniform(np.log(0.25), np.log(4.0),
                            prob.n_op)).astype(np.float32)
    ts = np.exp(rng.uniform(np.log(0.25), np.log(4.0),
                            prob.n_st)).astype(np.float32)
    return to, ts


@pytest.mark.parametrize("scenario", _DEFAULT_SCENARIOS, ids=_SCN_IDS)
@given(seed=st.integers(0, 2 ** 31 - 1))
@settings(max_examples=5, deadline=None)
def test_condensed_equals_wavefront_for_random_theta(scenario, seed):
    """``condense_aidg`` is exact for EVERY θ on the hard path: the
    condensed engine's cycles match the uncondensed wavefront across
    log-uniform θ draws, on every default cell (compiled kernels are
    cached, so each draw is one cheap evaluation)."""
    prob = compile_scenario(scenario).problem
    to, ts = _theta_draw(prob, seed)
    wf = sweep(prob, to[None], ts[None], engine="wavefront")[0]
    cd = sweep(prob, to[None], ts[None], engine="condensed")[0]
    assert abs(wf - cd) <= 0.5 + 1e-4 * abs(wf), (scenario.name, wf, cd)


@pytest.mark.parametrize("tau", [0.05, 0.01])
@pytest.mark.parametrize(
    "scenario",
    [s for s in _DEFAULT_SCENARIOS
     if s.name in ("oma/gemm", "gamma/gemm", "tpu_v5e/gemm")],
    ids=lambda s: s.name if hasattr(s, "name") else s)
@given(seed=st.integers(0, 2 ** 31 - 1))
@settings(max_examples=4, deadline=None)
def test_condensed_soft_bounds_for_random_theta(scenario, tau, seed):
    """On the τ-soft path the condensed evaluator (exact chain sums) stays
    between the hard result and the uncondensed soft upper bound, for
    random θ — the gradient engine descends a consistent surface."""
    prob = compile_scenario(scenario).problem
    to, ts = _theta_draw(prob, seed)
    to_j, ts_j = jnp.asarray(to), jnp.asarray(ts)
    hard = float(evaluate_theta(prob, to_j, ts_j))
    s_wf = float(evaluate_theta_soft(prob, to_j, ts_j, tau))
    s_cd = float(evaluate_theta_soft(prob, to_j, ts_j, tau,
                                     engine="condensed"))
    assert s_cd >= hard * (1 - 1e-3) - 1e-2, (scenario.name, s_cd, hard)
    assert s_cd <= s_wf * (1 + 1e-3) + 1e-2, (scenario.name, s_cd, s_wf)


# ---------------------------------------------------------------------------
# pareto_front invariants (repro.core.aidg.explorer)
# ---------------------------------------------------------------------------

# a coarse grid of finite objective values: duplicates and exact ties are
# *likely*, which is exactly the regime where frontier bugs hide.  Row
# counts are drawn uniformly up to 256, so inputs often span several of
# pareto_front's 32-row sweep blocks
_objective = st.integers(0, 8).map(lambda v: v / 4.0)
_MAX_ROWS = 256


def _rows_of(row):
    return st.integers(1, _MAX_ROWS).flatmap(
        lambda n: st.lists(row, min_size=n, max_size=n)).map(
            lambda r: np.asarray(r, np.float64))


_obj_rows = _rows_of(st.tuples(_objective, _objective))


def _dominates(a, b):
    return bool(np.all(a <= b) and np.any(a < b))


@given(_obj_rows)
@settings(**SETTINGS)
def test_pareto_front_mutually_nondominated(objs):
    front = pareto_front(objs)
    assert front.size > 0
    for i in front:
        for j in front:
            if i != j:
                assert not _dominates(objs[j], objs[i]), (i, j)


@given(_obj_rows)
@settings(**SETTINGS)
def test_pareto_front_dominates_every_excluded_row(objs):
    front = pareto_front(objs)
    kept = set(front.tolist())
    for j in range(len(objs)):
        if j not in kept:
            assert any(_dominates(objs[i], objs[j]) or
                       np.array_equal(objs[i], objs[j]) for i in front), j


@given(_obj_rows, st.integers(0, 2 ** 31 - 1))
@settings(**SETTINGS)
def test_pareto_front_deterministic_under_permutation(objs, seed):
    f1 = pareto_front(objs)
    assert np.array_equal(f1, pareto_front(objs))        # same input, twice
    perm = np.random.default_rng(seed).permutation(len(objs))
    f2 = pareto_front(objs[perm])
    pts = lambda o, idx: sorted(map(tuple, o[idx]))
    assert pts(objs, f1) == pts(objs[perm], f2)          # same point set
    assert np.all(np.diff(objs[f1, 0]) >= 0)             # sorted by obj 0


@given(_obj_rows)
@settings(**SETTINGS)
def test_pareto_front_keeps_exactly_one_of_duplicates(objs):
    # force at least one exact duplicate pair
    objs = np.concatenate([objs, objs[:1]])
    front = pareto_front(objs)
    pts = [tuple(objs[i]) for i in front]
    assert len(pts) == len(set(pts))                     # no duplicate points
    for i in front:                                      # first occurrence wins
        first = int(np.nonzero((objs == objs[i]).all(axis=1))[0][0])
        assert i == first, (i, first)


# the same invariants in 3 objectives — (latency, energy, cost), the
# frontier Explorer.explore and serve._rank actually rank
_obj_rows3 = _rows_of(st.tuples(_objective, _objective, _objective))


@given(_obj_rows3)
@settings(**SETTINGS)
def test_pareto_front_3d_dominance_consistent(objs):
    front = pareto_front(objs)
    assert front.size > 0
    kept = set(front.tolist())
    for i in front:                      # mutually non-dominated
        for j in front:
            if i != j:
                assert not _dominates(objs[j], objs[i]), (i, j)
    for j in range(len(objs)):           # excluded => dominated or duplicate
        if j not in kept:
            assert any(_dominates(objs[i], objs[j]) or
                       np.array_equal(objs[i], objs[j]) for i in front), j


@given(_obj_rows3, st.integers(0, 2 ** 31 - 1))
@settings(**SETTINGS)
def test_pareto_front_3d_deterministic_under_permutation(objs, seed):
    f1 = pareto_front(objs)
    assert np.array_equal(f1, pareto_front(objs))
    perm = np.random.default_rng(seed).permutation(len(objs))
    f2 = pareto_front(objs[perm])
    pts = lambda o, idx: sorted(map(tuple, o[idx]))
    assert pts(objs, f1) == pts(objs[perm], f2)
    assert np.all(np.diff(objs[f1, 0]) >= 0)             # sorted by obj 0


@given(_obj_rows3, st.integers(0, 2 ** 31 - 1),
       st.sampled_from([np.nan, np.inf, -np.inf]))
@settings(**SETTINGS)
def test_pareto_front_3d_nonfinite_rows_never_enter(objs, seed, bad):
    """A diverged candidate (NaN/inf in any objective) is dropped with a
    warning and can neither enter the 3-D frontier nor displace a finite
    row that the clean input would have kept."""
    rng = np.random.default_rng(seed)
    dirty = objs.copy()
    k = int(rng.integers(0, len(objs)))
    dirty[k, int(rng.integers(0, 3))] = bad
    with pytest.warns(RuntimeWarning, match="non-finite"):
        front = pareto_front(dirty)
    assert k not in front.tolist()
    for i in front:
        assert np.all(np.isfinite(dirty[i]))
    # brute-force oracle over the finite rows: non-dominated, first
    # occurrence of each duplicate point
    rows = [i for i in range(len(dirty))
            if np.all(np.isfinite(dirty[i]))]
    want = [i for i in rows
            if not any(_dominates(dirty[j], dirty[i]) or
                       (j < i and np.array_equal(dirty[j], dirty[i]))
                       for j in rows if j != i)]
    assert sorted(front.tolist()) == sorted(want)


# ---------------------------------------------------------------------------
# micro-batcher contract (repro.serve.batcher)
# ---------------------------------------------------------------------------

from repro.serve.batcher import MicroBatcher, plan_batches  # noqa: E402


@given(st.integers(0, 200), st.integers(1, 17))
@settings(**SETTINGS)
def test_plan_batches_is_a_greedy_fifo_partition(n, k):
    plan = plan_batches(n, k)
    flat = [i for s, e in plan for i in range(s, e)]
    assert flat == list(range(n))                 # tiles [0, n) exactly
    assert all(1 <= e - s <= k for s, e in plan)  # bounded windows
    assert all(e - s == k for s, e in plan[:-1])  # only the tail is short


@given(st.lists(st.integers(0, 9), min_size=0, max_size=40),
       st.integers(1, 7))
@settings(max_examples=20, deadline=None)
def test_microbatcher_dispatches_partition_the_query_set(items, k):
    """Every submitted item lands in exactly one dispatch — no drop, no
    dup — batches are contiguous in arrival order and bounded, and each
    result lands on ITS submitter's future."""
    def dispatch(batch):
        return [x * 10 + 1 for x in batch]

    mb = MicroBatcher(dispatch, max_batch=k, window_s=0.0)
    try:
        futs = [mb.submit(x) for x in items]
        results = [f.result(timeout=30.0) for f in futs]
        mb.drain()
        assert results == [x * 10 + 1 for x in items]
        seqs = [s for b in mb.dispatch_log for s in b]
        assert seqs == list(range(len(items)))    # partition, FIFO-contiguous
        assert all(1 <= len(b) <= k for b in mb.dispatch_log)
    finally:
        mb.close()


@given(st.lists(st.integers(0, 5), min_size=1, max_size=24),
       st.integers(1, 6), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_microbatcher_answers_invariant_to_interleaving(items, k, seed):
    """Stage random burst patterns with ``hold()`` so batch composition
    varies per draw: each item's answer is a pure function of the item,
    never of its batchmates or window shape, and the dispatch log stays
    a partition under EVERY interleaving."""
    def dispatch(batch):
        return [x * x + 1 for x in batch]

    rng = np.random.default_rng(seed)
    mb = MicroBatcher(dispatch, max_batch=k, window_s=0.0)
    try:
        futs = []
        i = 0
        while i < len(items):
            burst = int(rng.integers(1, k + 2))
            with mb.hold():                        # one staged window
                for x in items[i: i + burst]:
                    futs.append(mb.submit(x))
            i += burst
        results = [f.result(timeout=30.0) for f in futs]
        mb.drain()
        assert results == [x * x + 1 for x in items]
        assert sorted(s for b in mb.dispatch_log
                      for s in b) == list(range(len(items)))
    finally:
        mb.close()


@given(st.lists(st.booleans(), min_size=1, max_size=12), st.integers(1, 4))
@settings(max_examples=15, deadline=None)
def test_microbatcher_exception_fails_exactly_its_batch(flags, k):
    """A dispatch that raises fails every future in THAT batch and no
    other; the log still partitions the submissions."""
    def dispatch(batch):
        if any(batch):
            raise RuntimeError("poisoned batch")
        return [0 for _ in batch]

    mb = MicroBatcher(dispatch, max_batch=k, window_s=0.0)
    try:
        futs = [mb.submit(b) for b in flags]
        mb.drain()
        assert sorted(s for b in mb.dispatch_log
                      for s in b) == list(range(len(flags)))
        for batch in mb.dispatch_log:
            poisoned = any(flags[s] for s in batch)
            for s in batch:
                if poisoned:
                    with pytest.raises(RuntimeError):
                        futs[s].result(timeout=30.0)
                else:
                    assert futs[s].result(timeout=30.0) == 0
    finally:
        mb.close()


# ---------------------------------------------------------------------------
# staged-oracle routing + surrogate monotonicity (repro.serve + surrogate)
# ---------------------------------------------------------------------------

import functools  # noqa: E402


@functools.lru_cache(maxsize=1)
def _tiny_ex():
    """A 3-cell operator explorer, built once per process (compiled
    scenarios are cached, so this is cheap after the first call)."""
    from repro.core.aidg.explorer import Explorer
    return Explorer(scenarios=_DEFAULT_SCENARIOS[:3])


@functools.lru_cache(maxsize=1)
def _tiny_bundle():
    """A small fixed-seed surrogate over the tiny explorer (reduced
    sample/step budget — these properties test routing and structure,
    not accuracy)."""
    from repro.surrogate import SurrogateConfig, train_surrogate
    return train_surrogate(_tiny_ex(),
                           SurrogateConfig(n_samples=48, steps=200))


@given(st.lists(st.integers(0, 2), min_size=1, max_size=12),
       st.floats(0.0, 1.0), st.integers(1, 4))
@settings(max_examples=15, deadline=None)
def test_staged_routing_never_drops_dups_or_reorders(picks, max_err, k):
    """Whatever the confidence threshold — 0 routes everything to the
    packed tier, 1 routes (nearly) everything to the surrogate — every
    query gets exactly one answer, in submission order, for its own
    question; and the tier counters account for every fresh query."""
    from repro.serve import DSEService, Query
    ex = _tiny_ex()
    svc = DSEService(ex, pool=8, surrogate=_tiny_bundle(),
                     surrogate_max_err=max_err, max_batch=k)
    try:
        queries = [Query.make(workload=ex.compiled[i].workload,
                              archs=ex.compiled[i].arch) for i in picks]
        answers = svc.query_many(queries)
        assert len(answers) == len(queries)
        for q, a in zip(queries, answers):
            assert a.query == q                      # no reorder, no swap
            assert a.tier in ("surrogate", "packed")
            if a.tier == "surrogate":
                assert 0.0 < a.err_bound <= max_err
        st_ = svc.stats()
        fresh = st_["tiers"]["surrogate"] + st_["tiers"]["packed"]
        accounted = (fresh + st_["cache"]["hits"] + st_["cache"]["coalesced"])
        assert accounted == len(queries)
        # re-asking is answered from the cache, preserving the tier label
        again = svc.query_many(queries)
        for a, b in zip(answers, again):
            assert b.cached and b == a and b.tier == a.tier
    finally:
        svc.close()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
@given(st.lists(st.sampled_from(["ok", "error", "poison", "kill"]),
                min_size=0, max_size=10),
       st.integers(1, 3), st.integers(1, 4))
@settings(max_examples=15, deadline=None)
def test_no_fault_schedule_drops_dups_or_reorders(actions, attempts, k):
    """The robustness half of the serving contract: under ANY injected
    fault schedule — transient dispatch errors, poisoned payloads, even
    worker-thread kills — every submitted query resolves to exactly one
    outcome, in submission order, that is either an Answer to ITS OWN
    question or a structured failure.  Nothing hangs, drops, duplicates,
    or gets a batchmate's answer."""
    from repro.serve import (Answer, CircuitBreaker, DSEService, Query,
                             RetryPolicy, WorkerKill)
    from repro.serve.errors import ServeError

    spec = ";".join(f"packed[{i}]={a}"
                    for i, a in enumerate(actions) if a != "ok")
    ex = _tiny_ex()
    svc = DSEService(ex, pool=8, max_batch=k,
                     retry=RetryPolicy(max_attempts=attempts, base_s=0.0),
                     breaker=CircuitBreaker(open_after=2, probe_after=1),
                     fault_plan=spec or None)
    try:
        queries = [Query.make(workload="gemm", top_k=t)
                   for t in range(1, 9)]
        with svc.batcher.hold():                 # pin window composition
            futs = [svc.submit(q) for q in queries]
        outcomes = [f.exception(timeout=60.0) or f.result()
                    for f in futs]
        assert len(outcomes) == len(queries)     # no drop, no dup
        for q, o in zip(queries, outcomes):
            if isinstance(o, Answer):
                assert o.query == q              # no reorder, no swap
            else:
                assert isinstance(o, (ServeError, WorkerKill)), o
        # the schedule is finite: once it runs dry, walking the breaker
        # (shed -> probe) with an UNCACHED query must reach a clean
        # dispatch — each failed probe burns schedule, so the walk is
        # bounded by the schedule length
        probe = Query.make(workload="gemm", top_k=9)
        for _ in range(2 * len(actions) + 4):
            try:
                svc.query_many([probe])
                break
            except (ServeError, WorkerKill):
                continue
        else:
            pytest.fail("service never recovered after the schedule ran dry")
        final = svc.query_many(queries)
        for q, a in zip(queries, final):
            assert isinstance(a, Answer) and a.query == q
            assert a.tier == "packed"
    finally:
        svc.close()


@given(st.integers(0, 2 ** 31 - 1), st.integers(0, 4),
       st.floats(0.05, 2.0))
@settings(max_examples=20, deadline=None)
def test_surrogate_latency_monotone_in_each_knob(seed, knob, delta):
    """The exact engine's latency is provably nondecreasing in every θ
    knob (max/sum compositions of affine maps with nonnegative
    coefficients); the surrogate's closed form is monotone BY
    CONSTRUCTION (softplus-nonnegative path weights), so the property
    must hold exactly, for every cell, at any point and step size."""
    bundle = _tiny_bundle()
    rng = np.random.default_rng(seed)
    lo = np.exp(rng.uniform(np.log(0.25), np.log(4.0),
                            bundle.n_knobs)).astype(np.float32)
    knob = knob % bundle.n_knobs
    hi = lo.copy()
    hi[knob] = np.float32(min(4.0, hi[knob] + delta))
    lat, _ = bundle.predict_rel(np.stack([lo, hi]))
    assert np.all(lat[1] >= lat[0] - 1e-6 * np.abs(lat[0])), \
        (knob, lo[knob], hi[knob], lat)


@given(st.lists(st.integers(0, 255), min_size=1, max_size=60),
       st.integers(1, 4), st.integers(1, 4))
@settings(**SETTINGS)
def test_cache_hit_implies_faster(addrs, sets_pow, ways):
    """Invariant: a probe() hit always returns hit_latency."""
    c = SetAssociativeCache(name="c", sets=2 ** sets_pow, ways=ways,
                            hit_latency=1, miss_latency=9, cache_line_size=4)
    for a in addrs:
        hit_predicted = c.probe(a)
        lat = c.access_latency("read", a)
        assert lat == (1 if hit_predicted else 9)
