"""The condensed + matrix-packed evaluation engine:

(a) ``builder.condense_aidg`` — θ-parametric chain condensation (absorbed
    super-edges + affine-chain coupling) is EXACT on the hard max-plus
    path for every θ, per default cell, and actually shrinks the
    sequential scan on chain-dominated graphs (≥ 3x),
(b) ``maxplus.fixed_point_jax(engine="condensed")`` / the soft family —
    agreement with the wavefront engine, soft bounds,
(c) ``dse.PackedMatrix`` — the whole matrix in one dispatch: golden θ = 1
    pins hold exactly, network cells, pipelined composition, chunking,
    and the packed gradient path (packed-vs-wavefront and packed-vs-
    per-cell agreement live in tests/test_oracle_chain.py),
(d) storage static-order proofs and the prologue condensation boundary,
(e) the scenario-cache-stats autouse fixture isolates tests (regression).
"""

import numpy as np
import pytest

from repro.core.aidg.builder import condense_aidg
from repro.core.aidg.dse import PackSpec, PackedMatrix, sweep
from repro.core.aidg.explorer import (DEFAULT_SPACE, Explorer,
                                      compile_scenario, default_scenarios,
                                      random_candidates,
                                      scenario_cache_stats)
from repro.core.aidg.maxplus import fixed_point_jax, fixed_point_soft

from test_dse_explorer import GOLDEN_THETA1_CYCLES

SCENARIOS = default_scenarios()
IDS = [s.name for s in SCENARIOS]


@pytest.fixture(scope="module")
def ex_packed():
    return Explorer()                      # engine="packed" is the default


@pytest.fixture(scope="module")
def ex_wave():
    return Explorer(engine="wavefront")


# ---------------------------------------------------------------------------
# (a) condensation exactness + level reduction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario", SCENARIOS, ids=IDS)
def test_condensed_fixed_point_exact_at_theta_one(scenario):
    aidg = compile_scenario(scenario).aidg
    t_wf = np.asarray(fixed_point_jax(aidg, engine="wavefront"))
    t_cd = np.asarray(fixed_point_jax(aidg, engine="condensed"))
    assert np.array_equal(t_wf, t_cd), scenario.name


@pytest.mark.parametrize("scenario", SCENARIOS, ids=IDS)
def test_condensed_sweep_matches_wavefront_at_random_theta(scenario):
    prob = compile_scenario(scenario).problem
    rng = np.random.default_rng(hash(scenario.name) % 2 ** 31)
    B = 6
    to = rng.uniform(0.25, 4.0, (B, prob.n_op)).astype(np.float32)
    ts = rng.uniform(0.25, 4.0, (B, prob.n_st)).astype(np.float32)
    out_wf = sweep(prob, to, ts, engine="wavefront")
    out_cd = sweep(prob, to, ts, engine="condensed")
    assert np.allclose(out_wf, out_cd, rtol=1e-4, atol=0.5), scenario.name


@pytest.mark.parametrize("tau", [0.05, 0.01])
@pytest.mark.parametrize("scenario", SCENARIOS, ids=IDS)
def test_condensed_soft_bounded_by_hard_and_uncondensed_soft(scenario, tau):
    """The condensed soft family keeps absorbed/coupled steps as exact
    sums, so its makespan sits between the hard result and the (looser)
    uncondensed soft upper bound."""
    aidg = compile_scenario(scenario).aidg
    hard = float(np.asarray(fixed_point_jax(aidg)).max())
    s_wf = float(np.asarray(fixed_point_soft(aidg, tau=tau)).max())
    s_cd = float(np.asarray(
        fixed_point_soft(aidg, tau=tau, engine="condensed")).max())
    assert s_cd >= hard * (1 - 1e-3) - 1e-2, (scenario.name, s_cd, hard)
    assert s_cd <= s_wf * (1 + 1e-3) + 1e-2, (scenario.name, s_cd, s_wf)


def test_condensation_reduces_levels_on_chain_dominated_cells():
    """The tentpole's structural claim: ≥ 3x fewer sequential levels on
    the chain-dominated cell (scalar in-order OMA) and in total across
    the default matrix."""
    by_name = {s.name: s for s in SCENARIOS}
    oma = condense_aidg(compile_scenario(by_name["oma/gemm"]).aidg).stats
    assert oma["level_reduction"] >= 3.0, oma
    tot0 = tot1 = 0
    for s in SCENARIOS:
        st = condense_aidg(compile_scenario(s).aidg).stats
        assert st["levels_condensed"] <= st["levels"], s.name
        tot0 += st["levels"]
        tot1 += st["levels_condensed"]
    assert tot0 / tot1 >= 3.0, (tot0, tot1)


def test_fixed_point_soft_rejects_unknown_engine():
    aidg = compile_scenario(SCENARIOS[2]).aidg
    with pytest.raises(ValueError, match="engine"):
        fixed_point_soft(aidg, engine="blocked")


def test_condense_is_memoized_per_boundary():
    aidg = compile_scenario(SCENARIOS[2]).aidg   # gamma/gemm
    assert condense_aidg(aidg) is condense_aidg(aidg)
    b = condense_aidg(aidg, boundary=10)
    assert b is condense_aidg(aidg, boundary=10)
    assert b is not condense_aidg(aidg)


def test_condense_boundary_preserves_prefix_max():
    """With a prologue boundary, the max over KEPT nodes with original id
    < k equals the max over ALL nodes with id < k (the packed network
    prologue relies on this)."""
    sc = next(s for s in SCENARIOS if s.name == "oma/gemm")
    aidg = compile_scenario(sc).aidg
    t = np.asarray(fixed_point_jax(aidg, engine="condensed"))
    for k in (7, 63, 500):
        cond = condense_aidg(aidg, boundary=k)
        kept_below = cond.kept[cond.kept < k]
        assert kept_below.size, k
        assert t[kept_below].max() == pytest.approx(t[:k].max(), abs=1e-3), k


def test_storage_static_order_proofs():
    """The in-order OMA chain serves its D-cache in access order for every
    θ (provable: each access is an ancestor of the next); the systolic
    array's DRAM is genuinely dynamic (parallel lanes race)."""
    by_name = {s.name: s for s in SCENARIOS}
    oma = condense_aidg(compile_scenario(by_name["oma/gemm"]).aidg)
    assert oma.storage_static_order("dcache0")
    sy = condense_aidg(compile_scenario(by_name["systolic/gemm"]).aidg)
    assert not sy.storage_static_order("dram0")


def test_op_class_counts_cover_absorbed_nodes():
    cond = condense_aidg(compile_scenario(SCENARIOS[0]).aidg)  # oma/gemm
    counts = cond.op_class_counts()
    assert counts.sum() == cond.n_absorbed
    assert counts.shape[1] == len(cond.aidg.classes)


def test_longest_path_condensed_matches_wavefront():
    """The storage-free relaxation entry point (no queueing fold) agrees
    with the uncondensed wavefront node-for-node."""
    from repro.core.aidg.maxplus import (longest_path_condensed,
                                         longest_path_wavefront)
    aidg = compile_scenario(SCENARIOS[0]).aidg      # oma/gemm, one chain
    t_wf = np.asarray(longest_path_wavefront(aidg))
    t_cd = np.asarray(longest_path_condensed(aidg))
    assert np.array_equal(t_wf, t_cd)


# ---------------------------------------------------------------------------
# (c) the packed matrix: one dispatch, same numbers
# ---------------------------------------------------------------------------


def test_packed_theta_one_matches_golden_pins(ex_packed):
    """Acceptance: every cell's packed+condensed θ = 1 result matches the
    existing golden pins exactly."""
    for name, baseline in zip(ex_packed.scenario_names, ex_packed.baselines):
        assert float(baseline) == pytest.approx(
            GOLDEN_THETA1_CYCLES[name], abs=0.5), name


@pytest.mark.parametrize("width", [1, 300, 1024])
def test_packed_batch_equals_candidates_one_at_a_time(ex_packed, width):
    """The candidates ride the evaluator's trailing axis and nothing mixes
    them: a batch of any width (one, not a multiple of 128, the benchmark's
    1024) gives bitwise the cycles and energy of each candidate evaluated
    alone, θ = 1 and seeded random θ alike."""
    pm = ex_packed.packed_matrix()
    cand = np.concatenate([
        np.ones((1, ex_packed.space.n), np.float32),
        random_candidates(ex_packed.space, width - 1, seed=width,
                          include_baseline=False)])[:width]
    cycles, energy = pm.evaluate_full(cand)
    alone = [pm.evaluate_full(c[None]) for c in cand]
    assert np.array_equal(cycles, np.concatenate([c for c, _ in alone]))
    assert np.array_equal(energy, np.concatenate([e for _, e in alone]))
    assert np.array_equal(cycles[0], ex_packed.baselines)


def _mid_run_slot_ties(cell, storage):
    """Accesses of ``storage`` that, in the first queue pass at θ = 1,
    find two or more slots free at the same (nonzero) time."""
    aidg = cell.aidg
    t = np.asarray(fixed_point_jax(aidg, n_iters=0, engine="condensed"))
    nd = np.asarray(cell.problem.compiled_aidg.storage_scatter[storage])
    arrival = t[nd] - aidg.work[nd]
    order = np.argsort(arrival, kind="stable")
    free = np.zeros(aidg.storage_slots[storage])
    ties = 0
    for a, lat in zip(arrival[order],
                      np.asarray(aidg.storage_lat[storage])[order]):
        k = int(np.argmin(free))
        ties += int(free[k] > 0 and (free == free[k]).sum() > 1)
        free[k] = max(a, free[k]) + lat
    return ties


def test_packed_multi_slot_queue_ties_match_percell():
    """At θ = 1 the systolic array's 4-slot DRAM queue finds two slots
    free at the same time again and again; the packed evaluator's
    select-based slot update (the first such slot on a tie) gives the
    per-cell condensed engine's result."""
    cell = compile_scenario(next(s for s in SCENARIOS
                                 if s.name == "systolic/gemm"))
    assert _mid_run_slot_ties(cell, "dram0") > 0
    ex = Explorer(scenarios=[cell.scenario])
    kt = np.concatenate([np.ones((1, ex.space.n), np.float32),
                         random_candidates(ex.space, 3, seed=5,
                                           include_baseline=False)])
    packed = ex.evaluate(kt)[:, 0]
    percell = np.asarray(cell.evaluate(ex.space, kt, ex._projections[0],
                                       n_iters=ex.n_iters,
                                       engine="condensed"))
    assert packed[0] == percell[0]
    assert np.allclose(packed, percell, rtol=5e-3, atol=0.5), (packed,
                                                               percell)


def test_packed_chunked_evaluate_matches(ex_packed):
    cand = random_candidates(ex_packed.space, 23, seed=9)
    full = ex_packed.evaluate(cand)
    chunked = ex_packed.evaluate(cand, chunk=8)
    assert np.allclose(full, chunked, rtol=1e-6)


def test_packed_explore_deterministic(ex_packed):
    cand = random_candidates(ex_packed.space, 16, seed=11)
    r1 = ex_packed.explore(cand)
    r2 = ex_packed.explore(cand)
    assert np.array_equal(r1.cycles, r2.cycles)
    assert np.array_equal(r1.pareto, r2.pareto)


def test_packed_stats_shape(ex_packed):
    st = ex_packed.packed_matrix().stats()
    assert st["rows"] == st["cells"] == len(SCENARIOS)
    assert st["levels_condensed"] <= st["levels"]
    assert st["buckets"] >= 1
    assert st["level_reduction"] >= 3.0


def test_pack_spec_operator_shape():
    cs = compile_scenario(SCENARIOS[2])
    spec = cs.pack_spec(DEFAULT_SPACE.projection(cs.problem))
    assert isinstance(spec, PackSpec)
    assert len(spec.problems) == 1 and spec.run_reps.tolist() == [1.0]
    assert spec.fits_within.tolist() == [0.0]   # no overlap gates


def test_packed_matrix_dedups_shared_problems():
    cs = compile_scenario(SCENARIOS[2])
    proj = DEFAULT_SPACE.projection(cs.problem)
    spec = cs.pack_spec(proj)
    pm = PackedMatrix.build([spec, spec], DEFAULT_SPACE.n)
    assert pm.n_cells == 2 and pm.n_rows == 1
    out = pm.evaluate(np.ones((1, DEFAULT_SPACE.n), np.float32))
    assert out.shape == (1, 2)
    assert out[0, 0] == out[0, 1]


def test_explorer_refine_rides_packed(ex_packed):
    """Coordinate descent on the default explorer goes through the packed
    evaluator and must still not regress from θ = 1."""
    best = ex_packed.refine(rounds=1, points=3)
    base = ex_packed.explore(np.ones((1, ex_packed.space.n), np.float32))
    ref = ex_packed.explore(best[None, :])
    assert (ref.latency[0] * ref.cost[0]
            <= base.latency[0] * base.cost[0] + 1e-6)


# ---------------------------------------------------------------------------
# network cells through the packed engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def net_packed():
    from repro.core.network import default_network_scenarios
    return Explorer(scenarios=default_network_scenarios(
        networks=["olmo_1b"], archs=["tpu_v5e", "gamma"]))


def test_packed_network_baseline_normalizes(net_packed):
    # per-cell agreement at random θ moved to tests/test_oracle_chain.py
    base = net_packed.explore(np.ones((1, 5), np.float32))
    assert base.latency[0] == pytest.approx(1.0, abs=1e-5)


def test_packed_pipelined_network_matches_stack():
    from repro.core.network.model import NetworkScenario
    pip = NetworkScenario("eyeriss", "whisper_small", mode="pipelined")
    ex = Explorer(scenarios=[pip])
    kt = np.asarray([[1.0] * 5, [0.5, 1.5, 0.8, 1.2, 0.9]], np.float32)
    packed = ex.evaluate(kt)[:, 0]
    stack = pip.compile().evaluate(DEFAULT_SPACE, kt)
    assert np.allclose(packed, stack, rtol=5e-3)


def test_packed_gradient_matches_finite_differences(net_packed):
    from repro.core.aidg.gradient import GradientExplorer
    ge = GradientExplorer(net_packed)
    assert ge._packed_fn is not None      # the packed grad path is active
    k0 = np.asarray([[0.8, 1.2, 0.9, 1.1, 1.0]], np.float32)
    # τ = 0.2 / eps = 1e-2 as in tests/test_gradient_dse.py: smaller τ
    # puts central differences across softmax (and queue-order) kinks
    tau = 0.2
    _, g = ge.value_and_grad(k0, tau)
    eps = 1e-2
    for i in range(5):
        kp, km = k0.copy(), k0.copy()
        kp[0, i] += eps
        km[0, i] -= eps
        vp, _ = ge.value_and_grad(kp, tau)
        vm, _ = ge.value_and_grad(km, tau)
        fd = (vp[0] - vm[0]) / (2 * eps)
        # value_and_grad returns the log-objective; compare directly
        assert abs(fd - g[0, i]) <= 5e-2 * max(1.0, abs(fd)), (i, fd, g[0, i])


def test_packed_gradient_refine_not_worse_than_start(net_packed):
    from repro.core.aidg.gradient import GradientExplorer
    ge = GradientExplorer(net_packed)
    res = ge.refine(starts=2, steps=5, seed=0)
    base = float(ge.hard_score(np.ones((1, 5), np.float32))[0])
    assert res.score <= base + 1e-6


def test_percell_gradient_path_matches_packed(ex_packed, ex_wave):
    """GradientExplorer keeps a per-cell fallback for non-packed
    explorers; both paths descend the same objective (soft surfaces are
    close, not identical — condensed chains keep exact sums)."""
    from repro.core.aidg.gradient import GradientExplorer
    gp = GradientExplorer(ex_packed)
    gc = GradientExplorer(ex_wave)
    assert gp._packed_fn is not None and gc._packed_fn is None
    k0 = np.asarray([[0.9, 1.1, 1.0, 1.2, 0.8]], np.float32)
    vp, dp = gp.value_and_grad(k0, 0.05)
    vc, dc = gc.value_and_grad(k0, 0.05)
    assert vp[0] == pytest.approx(vc[0], rel=2e-2)
    assert np.allclose(dp, dc, rtol=0.2, atol=5e-2)


# ---------------------------------------------------------------------------
# (e) cache-stats isolation (regression for the autouse fixture)
# ---------------------------------------------------------------------------


def test_cache_stats_isolated_part_one():
    """Generate cache traffic; the paired test below must not see it."""
    compile_scenario(SCENARIOS[0])
    compile_scenario(SCENARIOS[0])
    stats = scenario_cache_stats()
    assert stats["hits"] + stats["misses"] >= 2


def test_cache_stats_isolated_part_two():
    """Runs after part_one in file order: the autouse fixture must have
    zeroed the counters, so the traffic above is invisible here."""
    assert scenario_cache_stats() == {"hits": 0, "misses": 0}
    compile_scenario(SCENARIOS[0])
    stats = scenario_cache_stats()
    assert stats["hits"] + stats["misses"] == 1
