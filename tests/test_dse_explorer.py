"""Batched multi-architecture DSE engine (repro.core.aidg.explorer):

(a) the batched sweep at θ = 1 reproduces the cycle-accurate event
    simulator per (arch, workload) — exactly on the exact cells,
(b) the Pareto frontier is non-dominated and deterministic,
(c) the AIDG cache returns results identical to cold builds,
plus candidate generators, projection, chunking, and refinement.
"""

import numpy as np
import pytest

from repro.core.aidg import fixed_point_batch, fixed_point_jax, sweep
from repro.core.aidg.explorer import (DEFAULT_SPACE, Explorer,
                                      clear_scenario_cache, compile_scenario,
                                      default_scenarios, grid_candidates,
                                      pareto_front, random_candidates)

SCENARIOS = default_scenarios()
IDS = [s.name for s in SCENARIOS]

# Golden θ = 1 wavefront cycles per default cell, pinned as literals so an
# evaluator refactor cannot silently drift the baseline while staying inside
# each cell's sim_tol band.  Update ONLY when a change is *supposed* to move
# the estimate — and re-justify it against the event simulator (the second
# member of test_theta_one_golden_regression re-checks golden vs oracle).
GOLDEN_THETA1_CYCLES = {
    "oma/gemm": 3832.0,
    "systolic/gemm": 1187.0,
    "gamma/gemm": 2954.0,
    "gamma/attention": 980.0,
    "gamma/scan": 2753.0,
    "eyeriss/conv": 91.0,
    "plasticine/reduce": 91.0,
    "tpu_v5e/gemm": 3881.0,
    "tpu_v5e/attention": 225.0,
    "tpu_v5e/scan": 613.0,
}


@pytest.fixture(scope="module")
def explorer():
    return Explorer()


# ---------------------------------------------------------------------------
# (a) θ = 1 vs the event simulator, cell by cell
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario", SCENARIOS, ids=IDS)
def test_sweep_theta_one_matches_event_sim(scenario, explorer):
    cs = next(c for c in explorer.compiled if c.scenario.key == scenario.key)
    # Explorer.baselines IS the compiled sweep evaluated at θ = 1
    est = float(explorer.baselines[explorer.compiled.index(cs)])
    sim = cs.simulate()
    if scenario.sim_tol == 0.0:
        assert round(est) == sim, (cs.name, est, sim)
    else:
        assert abs(est - sim) / sim <= scenario.sim_tol, (cs.name, est, sim)


@pytest.mark.parametrize("scenario", SCENARIOS, ids=IDS)
def test_theta_one_golden_regression(scenario, explorer):
    """Golden regression: the wavefront θ = 1 estimate is pinned to a
    literal per cell.  The sim-agreement test above has a tolerance band on
    inexact cells, so an evaluator refactor could drift inside it unnoticed
    — this pin turns any drift into a loud, reviewed diff.  The golden
    value itself must stay within the cell's sim_tol of the oracle, so the
    pin can't ossify a wrong number either."""
    assert scenario.name in GOLDEN_THETA1_CYCLES, (
        f"new scenario {scenario.name}: add its θ=1 wavefront cycles to "
        f"GOLDEN_THETA1_CYCLES")
    golden = GOLDEN_THETA1_CYCLES[scenario.name]
    cs = next(c for c in explorer.compiled if c.scenario.key == scenario.key)
    est = float(explorer.baselines[explorer.compiled.index(cs)])
    assert est == pytest.approx(golden, abs=0.5), (cs.name, est, golden)
    sim = cs.simulate()
    tol = max(scenario.sim_tol, 1e-9)
    assert abs(golden - sim) / sim <= tol, (cs.name, golden, sim)


def test_matrix_has_exact_cell_and_required_extent():
    """The acceptance floor: >= 4 architectures, >= 3 workload kinds, and
    at least one (arch, workload) cell whose AIDG is cycle-exact."""
    archs = {s.arch for s in SCENARIOS}
    workloads = {s.workload for s in SCENARIOS}
    assert len(archs) >= 4 and len(workloads) >= 3
    assert any(s.sim_tol == 0.0 for s in SCENARIOS)


# ---------------------------------------------------------------------------
# (b) Pareto frontier: non-dominated, deterministic
# ---------------------------------------------------------------------------


def _dominates(a, b):
    return np.all(a <= b) and np.any(a < b)


def test_pareto_front_is_nondominated(explorer):
    cand = random_candidates(explorer.space, 64, seed=3)
    res = explorer.explore(cand)
    objs = np.stack([res.latency, res.energy, res.cost], axis=1)
    front = set(int(i) for i in res.pareto)
    assert front, "empty frontier"
    for i in front:
        for j in range(len(objs)):
            if j != i:
                assert not _dominates(objs[j], objs[i]), (j, i)
    # everything off the frontier is dominated by something on it
    for j in range(len(objs)):
        if j not in front:
            assert any(_dominates(objs[i], objs[j]) or
                       np.array_equal(objs[i], objs[j]) for i in front), j


def test_pareto_front_deterministic_and_order_invariant():
    rng = np.random.default_rng(0)
    objs = rng.uniform(0, 1, (200, 2))
    objs[17] = objs[3]  # exact duplicate: first occurrence wins
    f1 = pareto_front(objs)
    f2 = pareto_front(objs)
    assert np.array_equal(f1, f2)
    # sorted by first objective
    assert np.all(np.diff(objs[f1, 0]) >= 0)
    # permuting the rows keeps the same set of non-dominated POINTS
    perm = rng.permutation(len(objs))
    fp = pareto_front(objs[perm])
    pts = lambda idx, o: sorted(map(tuple, np.round(o[idx], 12)))
    assert pts(f1, objs) == pts(fp, objs[perm])
    assert 17 not in set(f1.tolist())


def test_pareto_front_ignores_nonfinite_rows():
    """Regression: a candidate whose sweep diverges (NaN/inf objectives)
    used to corrupt the lexsort-based frontier silently — an inf-latency
    row could enter the frontier purely by having the smallest cost, and a
    NaN row breaks the sort's ordering contract.  Non-finite rows must be
    dropped with a warning and never appear in (or displace) the result."""
    clean = np.asarray([[1.0, 3.0], [2.0, 2.0], [3.0, 1.0], [2.5, 2.5]])
    base = pareto_front(clean)
    dirty = np.concatenate([clean, [[np.nan, 0.1], [np.inf, 0.05],
                                    [0.01, np.nan], [-np.inf, -np.inf]]])
    with pytest.warns(RuntimeWarning, match="non-finite"):
        front = pareto_front(dirty)
    # identical frontier, by original-row index
    assert np.array_equal(front, base)
    assert not (set(front.tolist()) & {4, 5, 6, 7})
    # all-non-finite input yields an empty frontier, not a crash
    with pytest.warns(RuntimeWarning, match="non-finite"):
        assert pareto_front(dirty[4:]).size == 0
    # finite input stays warning-free
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        assert np.array_equal(pareto_front(clean), base)


def _pareto_front_mask(objectives):
    """The full (B, B) dominance mask ``pareto_front`` used before its
    block sweep: the reference its output must equal, index for index."""
    import warnings
    objs = np.asarray(objectives, np.float64)
    finite = np.isfinite(objs).all(axis=1)
    if not finite.all():
        warnings.warn("pareto_front: non-finite rows", RuntimeWarning)
        if not finite.any():
            return np.zeros(0, dtype=np.int64)
    rows = np.nonzero(finite)[0]
    sub = objs[rows]
    m = sub.shape[1]
    order = np.lexsort(tuple(sub[:, j] for j in range(m - 1, -1, -1)))
    ss = sub[order]
    dom = (ss[None, :, :] <= ss[:, None, :]).all(axis=2)
    dom &= np.tri(len(ss), k=-1, dtype=bool)
    return np.asarray(rows[order[~dom.any(axis=1)]], dtype=np.int64)


def _realistic_objectives(n, seed):
    """(n, 3) latency-, energy- and cost-like objectives of log-uniform θ
    over the default knob box, shaped like an explore call's."""
    rng = np.random.default_rng(seed)
    kt = random_candidates(DEFAULT_SPACE, n, seed=seed).astype(np.float64)
    k = kt.shape[1]
    a, e, w = (rng.uniform(0.1, 1.0, k) for _ in range(3))
    latency = (a / kt).sum(axis=1) / a.sum() + 0.3 / kt[:, :2].min(axis=1)
    energy = (e * np.sqrt(kt)).sum(axis=1) / e.sum() + 0.2 * latency
    cost = (w / kt).sum(axis=1)
    return np.stack([latency, energy, cost], axis=1)


def _front_case(kind, b, m, seed):
    rng = np.random.default_rng(seed)
    if kind == "uniform":
        return rng.uniform(0.0, 1.0, (b, m))
    objs = rng.integers(0, 5, (b, m)).astype(np.float64)   # heavy ties
    if b > 1:                                               # exact duplicates
        objs[rng.integers(0, b, b // 3)] = objs[rng.integers(0, b, b // 3)]
    if kind == "nonfinite":
        bad = rng.integers(0, b, max(1, b // 8))
        objs[bad, rng.integers(0, m, bad.size)] = rng.choice(
            [np.nan, np.inf, -np.inf], bad.size)
    return objs


_FRONT_CASES = [(kind, b, m) for kind in ("uniform", "ties", "nonfinite")
                for b in (1, 2, 31, 32, 33, 63, 64, 65, 257, 1024)
                for m in (2, 3, 4)] + [("realistic", 1024, 3)]


@pytest.mark.parametrize("kind,b,m", _FRONT_CASES,
                         ids=[f"{k}-{b}x{m}" for k, b, m in _FRONT_CASES])
def test_pareto_front_equals_full_dominance_mask(kind, b, m):
    """The block sweep returns the same indices in the same order as the
    (B, B) dominance mask, across block boundaries (block size 32), on
    ties, duplicates, non-finite rows and a sweep-shaped 1024 x 3 set."""
    import warnings
    seed = 1000 * b + 10 * m + len(kind)
    objs = (_realistic_objectives(b, seed) if kind == "realistic"
            else _front_case(kind, b, m, seed))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        want = _pareto_front_mask(objs)
    if np.isfinite(objs).all():
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            got = pareto_front(objs)
    else:
        with pytest.warns(RuntimeWarning, match="non-finite"):
            got = pareto_front(objs)
    assert got.dtype == np.int64
    assert np.array_equal(got, want), (got, want)


@pytest.mark.parametrize("m", [2, 3, 4])
def test_pareto_front_of_no_rows_is_empty(m):
    got = pareto_front(np.zeros((0, m)))
    assert got.dtype == np.int64 and got.size == 0
    assert np.array_equal(got, _pareto_front_mask(np.zeros((0, m))))


def test_baseline_candidate_has_unit_latency(explorer):
    """Normalization is self-consistent: the θ = 1 candidate scores exactly
    latency 1.0 because Explorer.baselines comes from the same compiled
    sweep evaluator."""
    res = explorer.explore(np.ones((1, explorer.space.n), np.float32))
    assert res.latency[0] == pytest.approx(1.0, abs=1e-5)
    # same self-consistency for the energy objective: the baselines come
    # from the same evaluate_full the sweep uses
    assert res.energy[0] == pytest.approx(1.0, abs=1e-5)


def test_explore_is_deterministic(explorer):
    cand = random_candidates(explorer.space, 32, seed=7)
    r1 = explorer.explore(cand)
    r2 = explorer.explore(cand)
    assert np.array_equal(r1.cycles, r2.cycles)
    assert np.array_equal(r1.pareto, r2.pareto)


def test_explore_counts_ranked_and_kept_rows(explorer):
    before = explorer.stats()
    res = explorer.explore(random_candidates(explorer.space, 48, seed=11))
    after = explorer.stats()
    assert after["pareto_calls"] == before["pareto_calls"] + 1
    assert after["pareto_rows"] == before["pareto_rows"] + 48
    assert after["pareto_kept"] == before["pareto_kept"] + len(res.pareto)


# ---------------------------------------------------------------------------
# (c) AIDG cache ≡ cold build
# ---------------------------------------------------------------------------


def test_scenario_cache_identical_to_cold_build():
    sc = next(s for s in SCENARIOS if s.name == "gamma/attention")
    cached1 = compile_scenario(sc, use_cache=True)
    cached2 = compile_scenario(sc, use_cache=True)
    assert cached1 is cached2  # the cache actually caches
    cold = compile_scenario(sc, use_cache=False)
    assert cold is not cached1
    for attr in ("work", "base", "preds", "pred_extra", "fu_lat", "mem_lat"):
        assert np.array_equal(getattr(cold.aidg, attr),
                              getattr(cached1.aidg, attr)), attr
    assert cold.baseline == cached1.baseline
    to = np.full((4, cold.problem.n_op), 0.5, np.float32)
    ts = np.full((4, cold.problem.n_st), 2.0, np.float32)
    assert np.array_equal(sweep(cold.problem, to, ts),
                          sweep(cached1.problem, to, ts))


def test_cache_key_distinguishes_builders():
    """Two scenarios sharing (arch, workload, params) but built by
    different callables must not alias in the cache."""
    from repro.core.aidg.explorer import Scenario
    sc = SCENARIOS[0]
    a = Scenario(sc.arch, sc.workload, lambda: sc.build(), sc.params)

    def other_build():
        return sc.build()

    b = Scenario(sc.arch, sc.workload, other_build, sc.params)
    assert a.key != b.key


def test_default_scenario_params_carry_builder_identity():
    """The S() helper wraps every builder in a lambda (one shared
    __qualname__), so params must embed the wrapped function's identity —
    otherwise same-(arch, workload, sizes) cells with different builders
    would alias in the AIDG cache."""
    for s in SCENARIOS:
        assert s.params[0][0] == "__builder__", s.name
    keys = [s.key for s in SCENARIOS]
    assert len(keys) == len(set(keys))


def test_fixed_point_batch_rejects_unknown_storage(explorer):
    aidg = explorer.compiled[0].aidg
    with pytest.raises(KeyError, match="unknown storage"):
        fixed_point_batch(aidg, storage_lats={
            "no_such_storage": np.ones((2, 4), np.float32)})


def test_clear_scenario_cache():
    sc = SCENARIOS[0]
    a = compile_scenario(sc)
    clear_scenario_cache()
    b = compile_scenario(sc)
    assert a is not b and a.baseline == b.baseline


# ---------------------------------------------------------------------------
# candidate generators, projection, chunking, refinement
# ---------------------------------------------------------------------------


def test_candidate_generators_shapes_and_bounds():
    space = DEFAULT_SPACE
    g = grid_candidates(space, points=3)
    assert g.shape == (3 ** space.n, space.n)
    r = random_candidates(space, 100, seed=1)
    assert r.shape == (100, space.n)
    assert np.all(r[0] == 1.0)  # baseline row
    lo = np.asarray([k.lo for k in space.knobs])
    hi = np.asarray([k.hi for k in space.knobs])
    for c in (g, r):
        assert np.all(c >= lo - 1e-6) and np.all(c <= hi + 1e-6)
    # grids are deterministic
    assert np.array_equal(g, grid_candidates(space, points=3))


def test_projection_identity_for_unmatched_classes(explorer):
    """Knob vectors at 1.0 must project to all-ones θ; unmatched classes
    stay at 1.0 for any knob values."""
    for cs in explorer.compiled:
        to, ts = explorer.space.theta_for(
            cs.problem, np.ones((1, explorer.space.n), np.float32))
        assert np.all(to == 1.0) and np.all(ts == 1.0)


def test_theta_for_rejects_wrong_candidate_width(explorer):
    """Candidates minted for a different DesignSpace must error, not
    silently misproject onto the identity column."""
    bad = np.ones((2, explorer.space.n + 1), np.float32)
    with pytest.raises(ValueError, match="knobs"):
        explorer.space.theta_for(explorer.compiled[0].problem, bad)


def test_chunked_sweep_matches_unchunked(explorer):
    cs = explorer.compiled[2]  # gamma/gemm
    rng = np.random.default_rng(5)
    B = 37  # deliberately not a multiple of the chunk
    to = rng.uniform(0.5, 2, (B, cs.problem.n_op)).astype(np.float32)
    ts = rng.uniform(0.5, 2, (B, cs.problem.n_st)).astype(np.float32)
    full = sweep(cs.problem, to, ts)
    chunked = sweep(cs.problem, to, ts, chunk=16)
    assert np.allclose(full, chunked, atol=1e-3)


def test_fixed_point_batch_matches_single(explorer):
    cs = explorer.compiled[3]  # gamma/attention
    aidg = cs.aidg
    rng = np.random.default_rng(9)
    works = np.maximum(1.0, aidg.work[None, :] *
                       rng.uniform(0.5, 2, (3, aidg.n))).astype(np.float32)
    batch = np.asarray(fixed_point_batch(aidg, works=works))
    for i in range(3):
        single = np.asarray(fixed_point_jax(aidg, work=works[i]))
        assert np.allclose(batch[i], single, atol=1e-3), i
    # batched storage latencies (works broadcast from the baseline)
    st = next(iter(aidg.storage_lat))
    lats = np.stack([aidg.storage_lat[st] * f for f in (0.5, 1.0, 2.0)])
    batch = np.asarray(fixed_point_batch(
        aidg, storage_lats={st: lats.astype(np.float32)}))
    for i, f in enumerate((0.5, 1.0, 2.0)):
        single = np.asarray(fixed_point_jax(
            aidg, storage_lat={st: aidg.storage_lat[st] * f}))
        assert np.allclose(batch[i], single, atol=1e-3), i


def test_refine_stays_in_bounds_and_does_not_regress(explorer):
    best = explorer.refine(rounds=1, points=5, objective="latency")
    lo = np.asarray([k.lo for k in explorer.space.knobs])
    hi = np.asarray([k.hi for k in explorer.space.knobs])
    assert np.all(best >= lo - 1e-6) and np.all(best <= hi + 1e-6)
    base = explorer.explore(np.ones((1, explorer.space.n), np.float32))
    ref = explorer.explore(best[None, :])
    assert ref.latency[0] <= base.latency[0] + 1e-6


def test_refine_never_regresses_from_offgrid_start(explorer):
    """Coordinate steps always include the incumbent level, so refining
    from a start that is not on the geomspace grid cannot end up worse."""
    start = np.asarray([0.7, 1.3, 0.9, 1.1, 0.8], np.float32)

    def score(theta):
        r = explorer.explore(theta[None, :])
        return float(r.latency[0] * r.cost[0])

    best = explorer.refine(start=start, rounds=1, points=2)
    assert score(best) <= score(start) + 1e-6


def test_cost_proxy_monotone(explorer):
    """Uniformly faster hardware must cost more."""
    fast = np.full((1, explorer.space.n), 0.5, np.float32)
    slow = np.full((1, explorer.space.n), 2.0, np.float32)
    assert explorer.cost_proxy(fast)[0] > explorer.cost_proxy(slow)[0]
