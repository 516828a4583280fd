"""The compiled AIDG engine (trace → AIDG → LevelSchedule → CompiledAIDG):

(a) evaluator equivalence on every ARCH_REGISTRY scenario cell —
    ``longest_path_wavefront == longest_path_scan == numpy longest_path``
    (exact) and ``fixed_point_jax(engine="wavefront")`` matches
    ``builder.longest_path_fixed_point``, including the θ-reweighted DSE
    path,
(b) the level schedule's invariants (predecessors strictly shallower,
    levels partition the nodes, level-major renumbering consistent),
(c) no silent accuracy loss on high-in-degree nodes: ``build_aidg`` widens
    the padded predecessor slots instead of dropping edges,
(d) the AIDG dataclass ships proper array defaults (no ``None`` sentinels),
(e) the blocked engine is device-resident and runs the Pallas max-plus
    kernel on the AIDG path.
"""

import functools
import warnings

import numpy as np
import pytest

from repro.core.aidg import builder as builder_mod
from repro.core.aidg.builder import (AIDG, compile_aidg,
                                     compute_level_schedule, longest_path,
                                     longest_path_fixed_point)
from repro.core.aidg.dse import compiled_sweep, make_problem, sweep
from repro.core.aidg.explorer import (Explorer, compile_scenario,
                                      default_scenarios)
from repro.core.aidg.maxplus import (ENGINES, fixed_point_jax,
                                     longest_path_blocked, longest_path_scan,
                                     longest_path_wavefront, slot_queue_scan)

SCENARIOS = default_scenarios()
IDS = [s.name for s in SCENARIOS]


# ---------------------------------------------------------------------------
# (a) evaluator equivalence, cell by cell
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario", SCENARIOS, ids=IDS)
def test_wavefront_and_scan_match_numpy_exactly(scenario):
    aidg = compile_scenario(scenario).aidg
    t_np = longest_path(aidg)
    t_wf = np.asarray(longest_path_wavefront(aidg), np.float64)
    t_sc = np.asarray(longest_path_scan(aidg), np.float64)
    assert np.array_equal(t_np, t_wf), scenario.name
    assert np.array_equal(t_np, t_sc), scenario.name


@pytest.mark.parametrize("scenario", SCENARIOS, ids=IDS)
def test_fixed_point_wavefront_matches_numpy_fixed_point(scenario):
    aidg = compile_scenario(scenario).aidg
    fp_np = longest_path_fixed_point(aidg)
    fp_wf = np.asarray(fixed_point_jax(aidg, engine="wavefront"))
    # same tolerance as the seed's scan-vs-numpy fixed-point check
    assert abs(fp_np.max() - fp_wf.max()) < 1.0, scenario.name


@pytest.mark.parametrize("scenario", SCENARIOS, ids=IDS)
def test_theta_reweighted_engines_agree(scenario):
    """The θ-reweighted DSE path gives the same cycles per engine."""
    prob = compile_scenario(scenario).problem
    rng = np.random.default_rng(11)
    B = 4
    to = rng.uniform(0.5, 2.0, (B, prob.n_op)).astype(np.float32)
    ts = rng.uniform(0.5, 2.0, (B, prob.n_st)).astype(np.float32)
    out_wf = sweep(prob, to, ts, engine="wavefront")
    out_sc = sweep(prob, to, ts, engine="scan")
    assert np.allclose(out_wf, out_sc, atol=0.5), scenario.name


def test_wavefront_is_default_engine():
    """``fixed_point_jax``/``compiled_sweep`` default to the wavefront."""
    from repro.core.aidg.maxplus import DEFAULT_ENGINE
    assert DEFAULT_ENGINE == "wavefront"
    prob = compile_scenario(SCENARIOS[2]).problem   # gamma/gemm
    assert compiled_sweep(prob, 2) is compiled_sweep(prob, 2, "wavefront")
    assert compiled_sweep(prob, 2) is not compiled_sweep(prob, 2, "scan")


def test_explorer_engine_knob():
    ex_wf = Explorer(engine="wavefront")
    ex_sc = Explorer(engine="scan")
    cand = np.asarray([[1.0] * ex_wf.space.n,
                       [0.5, 2.0, 1.0, 0.7, 1.5]], np.float32)
    assert np.allclose(ex_wf.evaluate(cand), ex_sc.evaluate(cand), atol=0.5)
    with pytest.raises(ValueError, match="engine"):
        Explorer(engine="nonsense")


def test_unknown_engine_raises():
    aidg = compile_scenario(SCENARIOS[2]).aidg
    with pytest.raises(ValueError, match="engine"):
        fixed_point_jax(aidg, engine="nope")
    assert set(ENGINES) == {"wavefront", "scan", "blocked", "condensed"}
    # the Explorer additionally accepts the matrix-packed evaluator (its
    # default), which is not a per-cell fixed-point engine
    from repro.core.aidg.explorer import (DEFAULT_EXPLORER_ENGINE,
                                          EXPLORER_ENGINES)
    assert set(EXPLORER_ENGINES) == set(ENGINES) | {"packed"}
    assert DEFAULT_EXPLORER_ENGINE == "packed"


# ---------------------------------------------------------------------------
# (b) level schedule invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario", SCENARIOS, ids=IDS)
def test_level_schedule_invariants(scenario):
    ca = compile_aidg(compile_scenario(scenario).aidg)
    a, s = ca.aidg, ca.schedule
    # every predecessor is strictly shallower
    for i in range(a.n):
        js = a.preds[i][a.preds[i] >= 0]
        assert (s.depth[js] < s.depth[i]).all(), (scenario.name, i)
    # the levels partition the nodes
    real = s.level_nodes[s.level_nodes < a.n]
    assert np.array_equal(np.sort(real), np.arange(a.n))
    # level-major renumbering is a consistent permutation
    assert np.array_equal(s.order[s.rank], np.arange(a.n))
    assert (np.diff(s.depth[s.order]) >= 0).all()
    # the schedule never deepens past the node count
    assert s.n_levels <= max(1, a.n)
    assert a.stats["n_levels"] == s.n_levels


def test_level_schedule_of_empty_graph():
    s = compute_level_schedule(np.zeros((0, 4), np.int32), 0)
    assert s.n_levels == 0 and s.width == 0 and s.parallelism == 0.0


# ---------------------------------------------------------------------------
# (c) high-in-degree nodes: edges are widened, never dropped
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario", SCENARIOS, ids=IDS)
def test_default_scenarios_have_no_pred_overflow(scenario):
    aidg = compile_scenario(scenario).aidg
    assert aidg.stats["pred_overflow"] == 0, scenario.name
    assert aidg.preds.shape[1] == builder_mod.MAX_PREDS


def test_pred_width_expands_instead_of_dropping_edges(monkeypatch):
    """Rebuilding with a tiny MAX_PREDS must widen the padding (warning
    emitted) and keep the longest path bit-identical — no silent accuracy
    loss from dropped edges."""
    sc = SCENARIOS[2]                        # gamma/gemm, in-degree up to 4
    from repro.core.acadl.sim import build_trace
    from repro.core.aidg.builder import build_aidg
    ag, prog = sc.build()
    trace = build_trace(ag, prog)
    ref = longest_path(build_aidg(ag, trace))

    monkeypatch.setattr(builder_mod, "MAX_PREDS", 2)
    ag2, prog2 = sc.build()
    trace2 = build_trace(ag2, prog2)
    with pytest.warns(RuntimeWarning, match="widening"):
        tight = build_aidg(ag2, trace2)
    assert tight.stats["pred_overflow"] > 0
    assert tight.preds.shape[1] == tight.stats["pred_width"] > 2
    assert np.array_equal(longest_path(tight), ref)
    # the compiled wavefront evaluator folds the widened slots too
    assert np.array_equal(np.asarray(longest_path_wavefront(tight),
                                     np.float64), ref)


def test_evaluators_handle_wide_preds_directly():
    """A hand-built AIDG with more predecessors than MAX_PREDS evaluates
    identically through numpy, scan, and wavefront."""
    rng = np.random.default_rng(0)
    n, width = 40, 20
    preds = np.full((n, width), -1, np.int32)
    extra = np.zeros((n, width), np.float32)
    for i in range(1, n):
        k = int(rng.integers(1, min(i, width) + 1))
        js = rng.choice(i, size=k, replace=False)
        preds[i, :k] = np.sort(js)[::-1]
        extra[i, :k] = rng.integers(0, 4, k)
    aidg = AIDG(n=n, work=rng.integers(1, 5, n).astype(np.float32),
                fu_lat=np.zeros(n, np.float32),
                mem_lat=np.zeros(n, np.float32),
                base=rng.integers(0, 9, n).astype(np.float32),
                preds=preds, pred_extra=extra)
    t_np = longest_path(aidg)
    assert np.array_equal(t_np, np.asarray(longest_path_scan(aidg),
                                           np.float64))
    assert np.array_equal(t_np, np.asarray(longest_path_wavefront(aidg),
                                           np.float64))
    assert np.allclose(t_np, longest_path_blocked(aidg, block=16), atol=0.5)


# ---------------------------------------------------------------------------
# (d) AIDG dataclass defaults
# ---------------------------------------------------------------------------


def test_aidg_metadata_defaults_are_arrays():
    aidg = AIDG(n=0, work=np.zeros(0, np.float32),
                fu_lat=np.zeros(0, np.float32),
                mem_lat=np.zeros(0, np.float32),
                base=np.zeros(0, np.float32),
                preds=np.zeros((0, 1), np.int32),
                pred_extra=np.zeros((0, 1), np.float32))
    for attr in ("op_class", "op_scale", "mem_words"):
        val = getattr(aidg, attr)
        assert isinstance(val, np.ndarray), attr
        assert val.shape == (0,), attr
    # distinct instances don't share the default arrays
    other = AIDG(n=0, work=np.zeros(0, np.float32),
                 fu_lat=np.zeros(0, np.float32),
                 mem_lat=np.zeros(0, np.float32),
                 base=np.zeros(0, np.float32),
                 preds=np.zeros((0, 1), np.int32),
                 pred_extra=np.zeros((0, 1), np.float32))
    assert aidg.op_class is not other.op_class
    # make_problem consumes the defaults without special-casing None
    prob = make_problem(aidg)
    assert prob.n_op == 0 and prob.n_st == 0


# ---------------------------------------------------------------------------
# (e) blocked engine: device-resident scan + Pallas kernel on the AIDG path
# ---------------------------------------------------------------------------


def test_blocked_matches_numpy_and_accepts_pallas():
    from repro.kernels.maxplus import maxplus_matmul_pallas
    aidg = compile_scenario(SCENARIOS[2]).aidg     # gamma/gemm
    t_np = longest_path(aidg)
    t_jnp = longest_path_blocked(aidg, block=64)
    t_pl = longest_path_blocked(
        aidg, block=64,
        matmul=functools.partial(maxplus_matmul_pallas, interpret=True))
    assert np.allclose(t_np, t_jnp, atol=0.5)
    assert np.allclose(t_np, t_pl, atol=0.5)


def test_blocked_engine_in_fixed_point():
    aidg = compile_scenario(SCENARIOS[2]).aidg
    fp_np = longest_path_fixed_point(aidg)
    fp_bl = np.asarray(fixed_point_jax(aidg, engine="blocked"))
    assert abs(fp_np.max() - fp_bl.max()) < 1.0


def test_slot_queue_single_slot_closed_form():
    """The slots == 1 cummax fast path equals the sequential reference."""
    rng = np.random.default_rng(3)
    arrival = np.sort(rng.integers(0, 50, 64)).astype(np.float32)
    lat = rng.integers(1, 9, 64).astype(np.float32)
    fast = np.asarray(slot_queue_scan(arrival, lat, 1))
    done, free = [], 0.0
    for a, l in zip(arrival, lat):
        free = max(float(a), free) + float(l)
        done.append(free)
    assert np.allclose(fast, np.asarray(done))
