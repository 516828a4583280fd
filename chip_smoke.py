#!/usr/bin/env python3
"""Smoke run of the packed DSE engine and its query service on a TPU.

    python chip_smoke.py               # one chip: build, evaluate, serve
    python chip_smoke.py --four-chips  # candidate-axis sharding, 4 chips

One chip: builds ``Explorer(networks=True)`` (all 31 cells, the four
networks at their published widths), evaluates a θ = 1 row plus 1024
random candidates on the TPU, and compares them with the same packed
program compiled for the host CPU in this process.  It then serves a few
queries through ``ServeFrontend`` and ``ServeClient`` on loopback, and
checks every answer against the host reference's ranking.

``--four-chips`` runs only the sharded path and what it is compared with:
``evaluate(sharded=True, n_devices=4)`` against chip 0 alone, and a
sharded ``DSEService`` against an unsharded one.

Times printed are set-up times (AIDG build, condensation, compiles) and
single calls, not a benchmark.  Any failed check exits non-zero.  The last
line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
There is no CPU fallback: without a TPU the script exits 1 before any
phase runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# Worst relative error allowed between the TPU and the host at random θ,
# for cycles and for energy.  Both run the same f32 program; only the
# order of XLA's reductions inside a row (prefix sums over up to ~2**12
# absorbed nodes) and the TPU's division differ, which moves a result by
# about sqrt(2**12) * 2**-24 ≈ 4e-6.  A reduced-precision matmul or a
# flipped queue order shows at 1e-3 and above.
REL_TOL = 1e-5

# candidates per chip in the four-chip phase (256 over 4 chips)
SHARD_ROWS = 64

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


class CompileLog:
    """Trace, lowering and backend-compile durations, from JAX's
    monitoring hooks, collected per phase."""

    def __init__(self):
        self.events = []

    def __call__(self, event, duration, *args, **kwargs):
        kind = _COMPILE_EVENTS.get(event)
        if kind is not None:
            self.events.append((kind, float(duration)))

    def report(self, phase: str, total_s: float) -> int:
        """Print the phase's set-up lines; returns its backend compiles."""
        ev, self.events = self.events, []
        compiles = [d for k, d in ev if k == "compile"]
        other = sum(d for k, d in ev if k != "compile")
        # nested traces are counted twice, so the rest may round below 0
        rest = max(0.0, total_s - other - sum(compiles))
        print(f"setup [{phase}]: {total_s:.2f} s wall = host work "
              f"{rest:.2f} s + trace+lower "
              f"{other:.2f} s + {len(compiles)} backend compile(s): "
              + ", ".join(f"{d:.2f} s" for d in compiles), flush=True)
        return len(compiles)


def tpu_devices(n: int):
    """The device check: JAX must report a TPU, with at least ``n``
    chips.  There is no CPU fallback."""
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    check(d.platform == "tpu",
          f"no TPU found: JAX reports platform {d.platform!r} "
          f"(this script has no CPU fallback)")
    check(len(devs) >= n, f"need {n} TPU chips, JAX reports {len(devs)}")
    return devs


def rel_err(a: np.ndarray, b: np.ndarray) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def smoke_queries(space):
    """Six distinct questions: the whole matrix, one network, one
    architecture, a pinned knob, a short top-k and a network subset."""
    from repro.serve import Query

    knob = space.knobs[0]
    pinned = float(knob.hi / 2)
    return [Query.make(),
            Query.make(workload="olmo_1b"),
            Query.make(archs=["systolic"]),
            Query.make(workload="gemm", overrides={knob.name: pinned}),
            Query.make(workload="whisper_small", top_k=3),
            Query.make(workload="falcon_mamba_7b", archs=["gamma",
                                                          "tpu_v5e"])]


def reference_front(ex, q, cand, cycles, energy, base_c, base_e):
    """The host reference's ranking of one query: Pareto front over
    (latency, energy, cost), each cell normalized by the reference's own
    θ = 1 cycles and energy (``base_c``, ``base_e``), latency-ascending."""
    from repro.core.aidg.explorer import pareto_front, resolve_cells

    cols = np.asarray(resolve_cells(ex.compiled, workload=q.workload,
                                    archs=q.archs))
    latency = (cycles[:, cols] / base_c[cols]).mean(axis=1)
    en = (energy[:, cols] / np.maximum(base_e[cols], 1e-30)).mean(axis=1)
    cost = ex.cost_proxy(cand)
    return pareto_front(np.stack([latency, en, cost], axis=1))[: q.top_k]


def pinned_block(space, pool, q):
    cand = pool.copy()
    for name, val in q.overrides:
        cand[:, space.names.index(name)] = val
    return cand


def one_chip() -> dict:
    import jax
    import jax.numpy as jnp

    devs = tpu_devices(1)
    tpu, cpu = devs[0], jax.devices("cpu")[0]
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.core.aidg.explorer import Explorer, random_candidates
    from repro.serve import DSEService, ServeClient, ServeFrontend

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)

    # -- build: AIDG build + condensation, then the batch-1 compile -------
    t0 = time.perf_counter()
    ex = Explorer(networks=True)
    log.report("Explorer(networks=True): AIDG build, condensation, "
               "batch-1 baseline dispatch", time.perf_counter() - t0)
    pm = ex.packed_matrix()
    st = pm.stats()
    print(f"matrix: cells={st['cells']} rows={st['rows']} "
          f"buckets={st['buckets']} scan_len={st['scan_len']}", flush=True)
    check(pm.n_cells == 31, f"expected 31 cells, built {pm.n_cells}")

    # -- evaluate on the TPU ----------------------------------------------
    space = ex.space
    cand = np.concatenate([np.ones((1, space.n), np.float32),
                           random_candidates(space, 1024, seed=0)])
    fn = pm._full_fn()
    t0 = time.perf_counter()
    c_dev, e_dev = jax.block_until_ready(fn(jnp.asarray(cand)))
    log.report(f"first evaluate_full, batch {cand.shape[0]}",
               time.perf_counter() - t0)
    for name, arr in (("cycles", c_dev), ("energy", e_dev)):
        check(arr.devices() == {tpu},
              f"{name} lived on {arr.devices()}, not on {tpu}")
    cycles, energy = np.asarray(c_dev), np.asarray(e_dev)
    check(cycles.shape == (cand.shape[0], 31) == energy.shape,
          f"output shapes {cycles.shape} / {energy.shape}")
    check(np.isfinite(cycles).all() and np.isfinite(energy).all(),
          "non-finite cycles or energy on the TPU")
    t0 = time.perf_counter()
    c_pub, e_pub = ex.evaluate_full(cand)
    print(f"Explorer.evaluate_full, batch {cand.shape[0]}, compiled: "
          f"{time.perf_counter() - t0:.3f} s (one call, not a benchmark)",
          flush=True)
    check(np.array_equal(c_pub, cycles) and np.array_equal(e_pub, energy),
          "Explorer.evaluate_full differs from the packed dispatch")

    # -- the same packed program on the host ------------------------------
    queries = smoke_queries(space)
    blocks = {q.overrides: pinned_block(space, cand, q) for q in queries}
    ref = {}
    t0 = time.perf_counter()
    with jax.default_device(cpu):
        for sig, block in blocks.items():
            rc, re = jax.block_until_ready(fn(jax.device_put(block, cpu)))
            check(rc.devices() == {cpu}, f"reference ran on {rc.devices()}")
            ref[sig] = (np.asarray(rc), np.asarray(re))
    log.report(f"host reference, {len(blocks)} block(s) on {cpu}",
               time.perf_counter() - t0)
    ref_c, ref_e = ref[()]
    check(np.array_equal(cycles[0].view(np.uint32), ref_c[0].view(np.uint32)),
          "θ = 1 cycles differ between the TPU and the host: "
          + ", ".join(f"{ex.compiled[i].name}: {cycles[0, i]!r} vs "
                      f"{ref_c[0, i]!r}" for i in
                      np.flatnonzero(cycles[0] != ref_c[0])))
    print("θ = 1 cycles: bitwise equal to the host on all 31 cells",
          flush=True)
    err_c = rel_err(cycles[1:], ref_c[1:])
    err_e = rel_err(energy, ref_e)
    print(f"random θ, 1024 candidates: worst relative error cycles "
          f"{err_c:.3e}, energy {err_e:.3e} (tolerance {REL_TOL:.0e})",
          flush=True)
    check(err_c <= REL_TOL, f"cycles relative error {err_c:.3e}")
    check(err_e <= REL_TOL, f"energy relative error {err_e:.3e}")

    # -- serve through the RPC front-end ---------------------------------
    os.environ.pop("SERVE_FAULT_PLAN", None)
    t0 = time.perf_counter()
    with DSEService(ex, candidates=cand, chunk=cand.shape[0]) as svc, \
            ServeFrontend(svc) as fe, ServeClient(fe.address) as client:
        first = [client.query(q) for q in queries]
        again = [client.query(q) for q in queries]
        stats = client.stats()
    n_compiles = log.report(f"serve {2 * len(queries)} queries",
                            time.perf_counter() - t0)
    check(n_compiles == 0, f"serving compiled {n_compiles} new program(s)")
    check(stats["fault_plan"] is None and not stats["surrogate_armed"],
          "the service ran with a fault plan or a surrogate")
    check(stats["tiers"]["failed"] == 0,
          f"{stats['tiers']['failed']} failed answers")
    for q, a, b in zip(queries, first, again):
        check(a.tier == "packed" and b.tier == "packed",
              f"{q.key}: tiers {a.tier}, {b.tier}")
        check(not a.cached and b.cached and a == b,
              f"{q.key}: the repeat was not the cached answer")
        block = blocks[q.overrides]
        rc, re = ref[q.overrides]
        front = reference_front(ex, q, block, rc, re, ref_c[0], ref_e[0])
        want = tuple(float(v) for v in block[front[0]])
        check(a.best.theta == want,
              f"{q.key}: best design {a.best.theta} differs from the host "
              f"ranking's {want}")
        same_order = [d.theta for d in a.designs] == [
            tuple(float(v) for v in block[i]) for i in front]
        print(f"query {q.key}: tier={a.tier} best_arch={a.best_arch} "
              f"best latency={a.best.latency:.6f} matches host best; "
              f"top-{len(a.designs)} order equal: {same_order}", flush=True)
    print(f"serve: {stats['tiers']['packed']} packed answers, "
          f"{stats['tiers']['cache']} cache hits, "
          f"{stats['tiers']['failed']} failed, "
          f"{stats['device_dispatches']} device dispatches", flush=True)
    return {"platform": tpu.platform, "kind": tpu.device_kind,
            "count": len(devs)}


def four_chips() -> dict:
    import jax
    import jax.numpy as jnp

    devs = tpu_devices(4)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.core.aidg.explorer import Explorer, random_candidates
    from repro.serve import DSEService

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)

    t0 = time.perf_counter()
    ex = Explorer(networks=True)
    log.report("Explorer(networks=True)", time.perf_counter() - t0)
    pm = ex.packed_matrix()
    cand = random_candidates(ex.space, 4 * SHARD_ROWS, seed=0)

    # chip 0 alone runs the batch in chunks of one shard's rows, so both
    # sides compile the same per-device batch
    t0 = time.perf_counter()
    single_c, single_e = ex.evaluate_full(cand, chunk=SHARD_ROWS)
    log.report(f"single-device evaluate_full on {devs[0]}, batch 256 in "
               f"chunks of {SHARD_ROWS}", time.perf_counter() - t0)
    t0 = time.perf_counter()
    c_dev, e_dev = jax.block_until_ready(
        pm.sharded_fn(4)(jnp.asarray(cand)))
    log.report("sharded evaluate, 4 devices, batch 256",
               time.perf_counter() - t0)
    shards = sorted(c_dev.addressable_shards, key=lambda s: s.index[0].start)
    for s in shards:
        print(f"shard rows {s.index[0].start}:{s.index[0].stop} on "
              f"{s.device}", flush=True)
    check(len({s.device for s in shards}) == 4,
          f"the candidate axis spans {len({s.device for s in shards})} "
          f"devices, not 4")
    sharded_c, sharded_e = ex.evaluate_full(cand, sharded=True, n_devices=4)
    check(np.array_equal(np.asarray(c_dev), sharded_c),
          "sharded_fn and evaluate(sharded=True) differ")
    check(np.array_equal(single_c.view(np.uint32), sharded_c.view(np.uint32))
          and np.array_equal(single_e.view(np.uint32),
                             sharded_e.view(np.uint32)),
          "sharded evaluation is not bitwise equal to chip 0 alone")
    print("sharded cycles and energy: bitwise equal to chip 0 alone "
          "(256 candidates x 31 cells)", flush=True)

    os.environ.pop("SERVE_FAULT_PLAN", None)
    queries = smoke_queries(ex.space)
    t0 = time.perf_counter()
    with DSEService(ex, candidates=cand, chunk=SHARD_ROWS) as plain, \
            DSEService(ex, candidates=cand, chunk=cand.shape[0],
                       sharded=True, n_devices=4) as sharded:
        want = [plain.query(q) for q in queries]
        got = [sharded.query(q) for q in queries]
        st = sharded.stats()
    n_compiles = log.report(f"unsharded and sharded services, "
                            f"{len(queries)} queries each",
                            time.perf_counter() - t0)
    check(n_compiles == 0, f"serving compiled {n_compiles} new program(s)")
    check(st["sharded"] and st["tiers"]["failed"] == 0,
          "the sharded service failed answers")
    for q, a, b in zip(queries, want, got):
        check(a == b and b.tier == "packed",
              f"{q.key}: sharded answer differs from the unsharded one")
    print(f"sharded service: {len(queries)} answers equal to the "
          f"unsharded service's", flush=True)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": 4}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the candidate-axis sharded path over "
                         "4 chips and what it is compared with")
    args = ap.parse_args(argv)
    device = four_chips() if args.four_chips else one_chip()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
