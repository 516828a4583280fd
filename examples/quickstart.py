"""Quickstart: train a small LM end-to-end on CPU, then estimate its step
time on modeled accelerators with ACADL — per fused operator and for the
whole network.

    PYTHONPATH=src python examples/quickstart.py            # full demo
    PYTHONPATH=src python examples/quickstart.py --steps 20 # CI smoke
"""

import argparse

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.core.aidg import estimate_cycles
from repro.core.archs import TPU_V5E, make_tpu_v5e_ag
from repro.core.mapping.workload import map_to_tpu
from repro.launch.train import train_loop
from repro.models import SHAPES


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200,
                    help="training steps (use a small value for CI smoke)")
    args = ap.parse_args()

    # --- 1. train a reduced olmo-style model ------------------------------
    cfg = get_smoke_config("olmo-1b")
    print(f"training {cfg.arch_id} (smoke config, "
          f"{cfg.n_params()/1e6:.1f}M params, {args.steps} steps) ...")
    params, metrics = train_loop(cfg, steps=args.steps, batch=8, seq=128,
                                 ckpt_dir="/tmp/quickstart_ckpt",
                                 ckpt_every=max(10, args.steps // 2))
    losses = [r["loss"] for r in metrics.rows]
    print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f}")

    # --- 2. ACADL: how fast would the FULL olmo-1b train on a TPU-v5e? ----
    full = get_config("olmo-1b")
    shape = SHAPES["train_4k"]
    ag, _ = make_tpu_v5e_ag()
    prog = map_to_tpu(full, shape, per_device=256)
    cycles, _ = estimate_cycles(ag, prog)
    secs = cycles / (TPU_V5E["clock_ghz"] * 1e9)
    print(f"ACADL estimate: {full.arch_id} {shape.name} on 256 modeled "
          f"v5e chips: {secs*1e3:.1f} ms/step")

    # --- 3. network-level mapping: the whole DNN as a layer graph ---------
    # lower olmo-1b layer-by-layer onto the modeled TPU and compose the
    # per-layer AIDG makespans in max-plus (repro.core.network)
    import numpy as np
    from repro.core.aidg.explorer import DEFAULT_SPACE
    from repro.core.network import NetworkScenario

    for mode in ("sequential", "pipelined"):
        cn = NetworkScenario("tpu_v5e", "olmo_1b", mode=mode).compile()
        e2e = float(cn.evaluate(DEFAULT_SPACE,
                                np.ones((1, DEFAULT_SPACE.n), np.float32))[0])
        ms = e2e / (TPU_V5E["clock_ghz"] * 1e9) * 1e3
        print(f"network-level ({mode}): "
              f"{len(cn.layer_graph.instances)} layer instances -> "
              f"{cn.n_layers} unique AIDG programs, {e2e:.3e} cycles "
              f"({ms:.2f} ms) end-to-end decode step")


if __name__ == "__main__":
    enable_compile_cache()
    main()
