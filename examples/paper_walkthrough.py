"""Walk through the paper's three modeling examples end-to-end:

  §4.1  One MAC Accelerator (OMA)       — scalar level, Listing 5 GeMM
  §4.2  Parameterizable systolic array  — templates + dangling edges
  §4.3  Γ̈ [gœna]                        — fused-tensor level, Listing 4

and §6's timing simulation + the AIDG fast path ([16]).

    PYTHONPATH=src python examples/paper_walkthrough.py
"""

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.acadl import simulate
from repro.core.aidg import estimate_cycles
from repro.core.archs import make_gamma_ag, make_oma_ag, make_systolic_ag
from repro.core.mapping.gemm import (gamma_gemm, init_gemm_memory,
                                     oma_gemm_looped, oma_gemm_unrolled,
                                     read_gemm_result)
from repro.core.mapping.systolic import (init_systolic_memory,
                                         read_systolic_result,
                                         systolic_gemm_program)


def main():
    rng = np.random.default_rng(0)
    A = rng.integers(-3, 4, (8, 8)).astype(float)
    B = rng.integers(-3, 4, (8, 8)).astype(float)

    # --- §4.1 OMA ----------------------------------------------------------
    print("== OMA (scalar level, paper §4.1) ==")
    ag, _ = make_oma_ag()
    init_gemm_memory(ag, A, B)
    res = simulate(ag, oma_gemm_looped(8, 8, 8))
    ok = np.array_equal(read_gemm_result(ag, 8, 8), A @ B)
    print(f"  looped GeMM (Listing 5):   {res.cycles:6d} cycles  correct={ok}")
    ag, _ = make_oma_ag()
    init_gemm_memory(ag, A, B)
    res2 = simulate(ag, oma_gemm_unrolled(8, 8, 8, 4, 4, 4))
    print(f"  tiled/unrolled GeMM:       {res2.cycles:6d} cycles "
          f"({res.cycles / res2.cycles:.1f}x fewer)")

    # --- §4.2 systolic array -----------------------------------------------
    print("== Systolic array (templates + dangling edges, §4.2) ==")
    for r in (2, 4):
        ag, _ = make_systolic_ag(r, r)
        init_systolic_memory(ag, A, B)
        res = simulate(ag, systolic_gemm_program(8, 8, 8, r, r))
        ok = np.array_equal(read_systolic_result(ag, 8, 8), A @ B)
        print(f"  {r}x{r} PE grid:             {res.cycles:6d} cycles  correct={ok}")

    # --- §4.3 Γ̈ -------------------------------------------------------------
    print("== Γ̈ (fused-tensor level, §4.3) ==")
    Af = A.astype(np.float32); Bf = B.astype(np.float32)
    for nu in (1, 2):
        ag, _ = make_gamma_ag(n_units=nu)
        init_gemm_memory(ag, Af, Bf, memory="dram0", tile=8)
        units = tuple((f"lsu{k}", f"matMulFu{k}", f"vrf{k}") for k in range(nu))
        res = simulate(ag, gamma_gemm(8, 8, 8, tile=8, units=units,
                                      activation=1))
        C = read_gemm_result(ag, 8, 8, c_base=0x100000, memory="dram0", tile=8)
        ok = np.allclose(C, np.maximum(Af @ Bf, 0))
        print(f"  {nu} compute unit(s), fused ReLU: {res.cycles:5d} cycles  "
              f"correct={ok}")

    # --- §6 AIDG fast path ---------------------------------------------------
    print("== AIDG fast estimation (§6, [16]) ==")
    ag, _ = make_gamma_ag(n_units=2)
    init_gemm_memory(ag, Af, Bf, memory="dram0", tile=8)
    units = (("lsu0", "matMulFu0", "vrf0"), ("lsu1", "matMulFu1", "vrf1"))
    prog = gamma_gemm(8, 8, 8, tile=8, units=units)
    sim_cycles = simulate(ag, prog).cycles
    est, aidg = estimate_cycles(ag, prog)
    print(f"  event simulator: {sim_cycles} cycles; AIDG estimate: {est:.0f} "
          f"({aidg.n} nodes, {aidg.edges} edges)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
