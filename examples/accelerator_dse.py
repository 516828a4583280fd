"""Multi-architecture accelerator design-space exploration — the paper's
motivating use-case (§1: "selecting an accelerator that aligns with their
product's performance requirements"; §7: NAS / DNN-HW co-design loop).

Sweeps a shared 5-knob design space (matrix unit, vector unit, load/store,
on-chip SRAM, DRAM — multiplicative latency factors) over the FULL scenario
matrix: 6 modeled architectures x their mapped workloads (GEMM, conv,
attention, selective-scan, map-reduce), >= 1000 candidates per batch, one
batched JAX sweep per cached AIDG.  Reports the Pareto frontier of
(latency, energy, cost/area proxy) and two refinements of the incumbent: classic
derivative-free coordinate descent, and gradient descent through the
smooth max-plus relaxation (the sweep is pure JAX, so the makespan is
differentiable in the design knobs — batched multi-start projected Adam
needs half the candidate evaluations).  Finishes with whole-network cells
(``repro.core.network``): entire DNNs lowered layer-by-layer and
co-optimized against end-to-end latency, including the sequential vs
double-buffer-pipelined composition.

    PYTHONPATH=src python examples/accelerator_dse.py
"""

import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.aidg.explorer import (Explorer, grid_candidates,
                                      random_candidates)
from repro.core.aidg.gradient import GradientExplorer


def main():
    t0 = time.perf_counter()
    ex = Explorer()
    names = ex.scenario_names
    print(f"scenario matrix ({len(names)} cells, "
          f"compiled in {time.perf_counter() - t0:.2f}s):")
    for cs in ex.compiled:
        s = cs.schedule
        print(f"  {cs.name:20s} {cs.aidg.n:5d} instructions, "
              f"{s.n_levels:5d} wavefront levels "
              f"({s.parallelism:4.1f}x parallel), "
              f"baseline {cs.baseline:8.0f} cycles")

    # --- candidates: full factorial grid + log-uniform random ------------
    cand = np.concatenate([
        grid_candidates(ex.space, points=3),          # 3^5 = 243
        random_candidates(ex.space, 1024, seed=0),    # its row 0 (index 243
    ])                                                #  here) = baseline θ=1
    print(f"\nknobs: {ex.space.names}")
    print(f"candidates: {cand.shape[0]} "
          f"(x {len(names)} scenarios = {cand.shape[0] * len(names)} cells)")

    ex.explore(cand)  # warm-up: JIT-compile each scenario at this batch shape
    t0 = time.perf_counter()
    res = ex.explore(cand)
    dt = time.perf_counter() - t0
    thr = cand.shape[0] * len(names) / dt
    print(f"swept in {dt:.2f}s ({thr:.0f} (arch, workload, theta) configs/s, "
          "steady-state)")

    # --- Pareto frontier of (latency, energy, cost) -----------------------
    print(f"\nPareto frontier ({len(res.pareto)} non-dominated designs, "
          "latency = mean baseline-relative cycles, energy = mean "
          "baseline-relative energy, cost = area proxy):")
    frontier = res.frontier()
    step = max(1, len(frontier) // 8)
    for row in frontier[::step]:
        thetas = ", ".join(f"{n}x{row[f'theta[{n}]']:.2f}"
                           for n in ex.space.names)
        print(f"  latency {row['latency']:.3f}  "
              f"energy {row['energy']:.3f}  cost {row['cost']:6.2f}  "
              f"[{thetas}]")

    i = res.best
    print(f"\nbest latency*cost compromise (candidate {i}): "
          f"latency {res.latency[i]:.3f}, energy {res.energy[i]:.3f}, "
          f"cost {res.cost[i]:.2f}")
    per_scn = ", ".join(f"{n}={c:.0f}" for n, c in zip(names, res.cycles[i]))
    print(f"  cycles: {per_scn}")

    # --- coordinate-descent refinement ------------------------------------
    t0 = time.perf_counter()
    best = ex.refine(rounds=2, points=7)
    ref = ex.explore(best[None, :])
    cd_evals = (7 + 1) * ex.space.n * 2
    print(f"\ncoordinate descent ({time.perf_counter() - t0:.2f}s, "
          f"{cd_evals} candidates) -> latency {ref.latency[0]:.3f}, "
          f"cost {ref.cost[0]:.2f}, "
          f"product {ref.latency[0] * ref.cost[0]:.3f}")
    print("  theta:", {n: round(float(v), 3)
                       for n, v in zip(ex.space.names, best)})

    # --- gradient refinement over the smooth max-plus relaxation ----------
    # batched multi-start projected Adam in log-knob space, τ annealed from
    # a heavily smoothed landscape to a near-exact one; the final score is
    # re-judged by the hard evaluator (same objective as everything above)
    t0 = time.perf_counter()
    res = GradientExplorer(ex).refine()
    gref = ex.explore(res.theta[None, :])
    print(f"gradient descent ({time.perf_counter() - t0:.2f}s, "
          f"{res.evaluations} candidates) -> "
          f"latency {gref.latency[0]:.3f}, cost {gref.cost[0]:.2f}, "
          f"product {res.score:.3f}")
    print("  theta:", {n: round(float(v), 3)
                       for n, v in zip(ex.space.names, res.theta)})

    # --- whole networks as cells: the paper's actual artifact -------------
    # lower entire DNNs (layer graph -> per-layer AIDG -> max-plus
    # composition) onto a couple of architectures and co-optimize the SAME
    # shared knobs against end-to-end network latency
    from repro.core.network import NetworkScenario, default_network_scenarios

    t0 = time.perf_counter()
    nex = Explorer(scenarios=default_network_scenarios(
        networks=["whisper_small", "olmo_1b"], archs=["gamma", "tpu_v5e"]))
    print(f"\nnetwork matrix ({len(nex.scenario_names)} cells, compiled in "
          f"{time.perf_counter() - t0:.2f}s):")
    for i, cn in enumerate(nex.compiled):
        print(f"  {cn.name:24s} {len(cn.layer_graph.instances):4d} layer "
              f"instances -> {cn.n_layers} unique programs, "
              f"baseline {float(nex.baselines[i]):.3e} cycles end-to-end")
    theta = nex.refine(method="grad", starts=2, steps=10)
    nref = nex.explore(theta[None, :])
    print(f"  gradient co-design on end-to-end latency -> "
          f"latency {nref.latency[0]:.3f}, cost {nref.cost[0]:.2f}")

    # sequential vs double-buffer-pipelined composition of one cell
    seq = NetworkScenario("tpu_v5e", "olmo_1b").compile()
    pip = NetworkScenario("tpu_v5e", "olmo_1b", mode="pipelined").compile()
    one = np.ones((1, nex.space.n), np.float32)
    s = float(seq.evaluate(nex.space, one)[0])
    p = float(pip.evaluate(nex.space, one)[0])
    print(f"  olmo-1b on tpu_v5e: sequential {s:.3e} cycles, "
          f"pipelined {p:.3e} ({100 * (1 - p / s):.0f}% hidden by "
          f"double-buffered overlap)")

    # --- energy as a co-equal objective -----------------------------------
    # every architecture carries per-op-class pJ coefficients (per-tech-node
    # tables); the packed dispatch returns (cycles, energy) together, so
    # energy-targeted co-design reuses the same compiled kernel
    from repro.core.aidg.energy import energy_bottleneck_report
    from repro.core.archs.energy import energy_model

    eres = GradientExplorer(nex, objective="edp").refine(starts=2, steps=10)
    eref = nex.explore(eres.theta[None, :])
    print(f"\nenergy-delay co-design on the network matrix -> "
          f"latency {eref.latency[0]:.3f}, energy {eref.energy[0]:.3f}, "
          f"cost {eref.cost[0]:.2f}")
    print("  theta:", {n: round(float(v), 3)
                       for n, v in zip(nex.space.names, eres.theta)})

    # where do the joules go?  storage-node traffic x per-level access
    # energy, grouped by storage class (the ZigZag-style breakdown)
    em = energy_model("tpu_v5e")
    print(f"  memory-level energy bottlenecks, olmo-1b on tpu_v5e "
          f"({em.tech_nm} nm tables):")
    for row in energy_bottleneck_report(seq):
        print(f"    {row['storage_class']:7s} {row['words']:.3e} words "
              f"x {row['pj_per_word']:7.1f} pJ = "
              f"{row['energy_pj']:.3e} pJ ({100 * row['share']:.0f}%)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
