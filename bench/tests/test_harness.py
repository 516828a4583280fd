"""Tests of the chip benchmark's harness.  They run on the CPU and need no
chip: the harness's look for a TPU is skipped where a test drives a run.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import control  # noqa: E402
import correct  # noqa: E402
import loads  # noqa: E402
import profile_trace as tr  # noqa: E402
import run as bench  # noqa: E402

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
DATA = BENCH / "tests" / "data"
# the program's operator matrix, ten small cells: a configuration that the
# CPU builds and drives at a test's cost
OPS10 = DATA / "ops10.json"
CELLS = [("oma", "gemm"), ("systolic", "gemm"), ("gamma", "attention"),
         ("tpu_v5e", "gemm"), ("tpu_v5e", "whisper_small")]


def _json(path: Path):
    return json.loads(path.read_text())


# -- traffic ------------------------------------------------------------------


def _serve_mix(seed, seconds=30.0):
    knobs = _json(BENCH / "configs" / "net28.json")["knobs"]
    traffic = _json(BENCH / "traffic" / "zipf_serve_net28.json")
    cat = loads.catalog(CELLS, knobs, traffic, loads.rng_for(seed, 3))
    return cat, loads.schedule(cat, traffic, seconds,
                               loads.rng_for(seed, 4)), traffic


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_catalog_and_arrivals_repeat_for_a_seed_and_differ_between_seeds(
        seed):
    cat_a, a, traffic = _serve_mix(seed)
    cat_b, b, _ = _serve_mix(seed)
    cat_c, c, _ = _serve_mix(seed + 1)
    assert cat_a == cat_b and a.questions == b.questions
    assert np.array_equal(a.due, b.due) and np.array_equal(a.new, b.new)
    assert cat_a != cat_c and not np.array_equal(a.due, c.due)
    # a fixed count of arrivals, every one inside the window, every
    # question one that resolves to a cell
    assert len(a.due) == round(traffic["rate_qps"] * 30.0)
    assert a.due.min() >= 0 and a.due.max() < 30.0
    for q in a.questions + a.warm:
        assert any((q["workload"] in (None, w))
                   and (q["archs"] is None or arch in q["archs"])
                   for arch, w in CELLS)
    # new questions come in catalog order after the set-up questions
    firsts = [q for q, n in zip(a.questions, a.new) if n]
    k = len(a.warm)
    assert firsts == cat_a[k:k + len(firsts)]


def test_sweep_blocks_repeat_for_a_seed_and_differ_between_seeds():
    knobs = _json(OPS10)["knobs"]
    traffic = _json(BENCH / "traffic" / "sweep_block1024.json")
    a = loads.sweep_blocks(knobs, traffic, 3)
    b = loads.sweep_blocks(knobs, traffic, 3)
    c = loads.sweep_blocks(knobs, traffic, 4)
    assert len(a) == traffic["blocks"]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    assert np.all(a[0][0] == 1.0)        # the reference machine is ranked
    lo = np.array([k["lo"] for k in knobs])
    hi = np.array([k["hi"] for k in knobs])
    assert all(np.all((x >= lo) & (x <= hi)) for x in a)


def test_latency_counts_from_the_due_time_so_a_stall_delays_later_queries():
    class Client:
        def query(self, q):
            time.sleep(0.3 if q == "stall" else 0.001)
            return SimpleNamespace(cached=False)

        def close(self):
            pass

    sched = loads.Schedule(warm=[], due=np.array([0.0, 0.05, 0.1]),
                           questions=["stall", "a", "b"],
                           new=np.zeros(3, bool))
    loop = loads.OpenLoop(make_client=lambda a: Client(),
                          make_query=lambda q: q, clients=1)
    loop.open(None, sched)
    t0 = time.perf_counter() + 0.01
    recs = loop.run(sched, t0, grace_s=5.0)
    assert all(r.ok for r in recs)
    lat = [r.done - r.due for r in recs]
    late = [r.sent - r.due for r in recs]
    assert lat[0] >= 0.3
    # the later queries waited behind the stall, and the wait counts
    assert lat[1] >= 0.3 - 0.05 - 0.01 and late[1] >= 0.2
    assert lat[2] >= 0.3 - 0.1 - 0.01 and late[2] >= 0.15


def test_a_query_with_no_answer_is_recorded_as_failed():
    class Client:
        def query(self, q):
            raise ConnectionError("server closed the connection")

        def close(self):
            pass

    sched = loads.Schedule(warm=[], due=np.array([0.0]), questions=["a"],
                           new=np.ones(1, bool))
    loop = loads.OpenLoop(make_client=lambda a: Client(),
                          make_query=lambda q: q, clients=2)
    loop.open(None, sched)
    (rec,) = loop.run(sched, time.perf_counter(), grace_s=5.0)
    assert not rec.ok and "ConnectionError" in rec.error


# -- trace reduction and per-layer metrics -------------------------------------


def test_trace_reduction_on_a_hand_made_trace():
    devices = {"/device:TPU:0": [("a", 0.0, 10.0), ("b", 5.0, 20.0),
                                 ("c", 30.0, 40.0), ("d", 60.0, 70.0)]}
    spans = [("explore", 0.0, 25.0), ("evaluate_full", 0.0, 22.0),
             ("rank", 25.0, 45.0)]
    s = tr.summarize(devices, spans, 0.0, 50.0)
    assert s["busy_s"] == pytest.approx(30e-9)
    assert s["window_s"] == pytest.approx(50e-9)
    # gap 20-30: its middle, 25, lies in explore and in rank, rank is the
    # shorter; gap 40-50 lies in rank; "d" is outside the stretch
    assert s["idle_gaps"] == [["rank", pytest.approx(20e-9)]]
    assert [n for n, _ in s["device_ops"]] == ["b", "a", "c"]
    assert s["device_ops"][0][1] == pytest.approx(15e-9)


RECORDED = DATA / "trace_ops10_sweep.json"


def test_trace_reduction_on_the_recorded_trace():
    rec = _json(RECORDED)
    devices = {k: [tuple(e) for e in v] for k, v in rec["devices"].items()}
    spans = [tuple(e) for e in rec["spans"]]
    s = tr.summarize(devices, spans, rec["lo"], rec["hi"])
    want = rec["expected"]
    assert s["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    assert s["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    assert s["device_ops"] == [[n, pytest.approx(v, rel=1e-12)]
                               for n, v in want["device_ops"]]
    assert s["idle_gaps"] == [[n, pytest.approx(v, rel=1e-12)]
                              for n, v in want["idle_gaps"]]
    # brute force: busy time is the measure of the union of the events
    lo, hi = rec["lo"], rec["hi"]
    (evs,) = devices.values()
    edges = sorted({lo, hi} | {min(max(x, lo), hi) for _, s0, e0 in evs
                               for x in (s0, e0)})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s0 <= 0.5 * (a + b) <= e0 for _, s0, e0 in evs))
    assert s["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-12)
    assert 0 < s["busy_s"] < s["window_s"]


def _serve_run(trace):
    rec = lambda due, done, cached: SimpleNamespace(
        ok=True, due=due, done=done, answer=SimpleNamespace(cached=cached))
    records = [rec(0.0, 0.004, True), rec(0.1, 0.106, True),
               rec(0.2, 0.202, True), rec(0.3, 0.8, False),
               SimpleNamespace(ok=False, due=0.4, done=60.0, answer=None)]
    st = lambda cand, packed, t: {"dispatched_candidates": cand,
                                  "tiers": {"packed": packed},
                                  "tier_time_s": {"packed": t}}
    return {"kind": "serve", "pool": 256, "records": records,
            "stats_window": (st(512, 2, 1.0), st(512 + 2 * 256, 4, 2.2)),
            "stats_stretch": (st(512, 2, 1.0), st(512 + 4 * 256, 6, 3.0)),
            "trace": trace}


def _sweep_run(trace):
    return {"kind": "sweep", "calls": 30, "window_s": 30.0,
            "calls_in_stretch": 8, "packed_stats": {"scan_len": 544},
            "trace": trace}


@pytest.mark.parametrize("name,kind,want", [
    ("cached_answer_ms", "serve", 4.0),
    ("queries_per_dispatch", "serve", 2.0),
    ("packed_ms_per_query", "serve", 600.0),
    ("device_ms_per_dispatch", "serve", "busy/4"),
    ("device_idle_share.serve", "serve", "idle"),
    ("device_ms_per_call", "sweep", "busy/8"),
    ("packed_scan_steps", "sweep", 544.0),
    ("device_idle_share.sweep", "sweep", "idle"),
])
def test_each_metric_reads_its_known_value(name, kind, want):
    rec = _json(RECORDED)
    s = rec["expected"]
    run = (_serve_run if kind == "serve" else _sweep_run)(s)
    expected = {"busy/4": s["busy_s"] / 4 * 1e3,
                "busy/8": s["busy_s"] / 8 * 1e3,
                "idle": 100 * (1 - s["busy_s"] / s["window_s"])}.get(want,
                                                                      want)
    assert bench.read_metric(name, run) == pytest.approx(expected)
    if not name.startswith("device_idle_share."):    # reads the trace only
        other = (_sweep_run if kind == "serve" else _serve_run)(s)
        assert bench.read_metric(name, other) is None    # nothing to read


def test_a_split_metric_falls_back_to_its_quantitys_reader():
    for part in ("serve", "sweep"):
        assert bench.metric_file(f"device_idle_share.{part}") == \
            BENCH / "metrics" / "device_idle_share.py"
    assert bench.metric_file("packed_scan_steps") == \
        BENCH / "metrics" / "packed_scan_steps.py"


def test_a_share_of_the_device_is_never_reported_as_zero_without_a_trace():
    empty = {"window_s": 1.0, "busy_s": 0.0}
    for name in ("device_idle_share.sweep", "device_ms_per_call"):
        assert bench.read_metric(name, _sweep_run(empty)) is None


# -- the manifest, and files found by name -------------------------------------


def test_manifest_names_units_and_cells():
    m = _json(ROOT / "BENCHMARK.json")
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    e2e = {x["name"]: x for x in m["end_to_end"]}
    for x in m["end_to_end"] + m["per_layer"]:
        assert name.match(x["name"]) and unit.match(x["unit"])
        assert x["better"] in ("lower", "higher")
    for x in m["per_layer"]:
        for w in x["workloads"]:
            assert w in e2e[x["moves"]].get("workloads", [w])
    for w in m["workloads"]:
        assert name.match(w["name"]) and w["chips"] == 1
        assert (BENCH / "cells" / f"{w['name']}.json").exists()
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        reported = bench.metric_names(m, w["name"], "per_layer")
        assert reported and "setup_s" in bench.metric_names(
            m, w["name"], "end_to_end")
        for p in reported:
            assert bench.metric_file(p).exists()
    for c in m["configs"]:
        cfg = _json(ROOT / c["file"])
        assert cfg["reduced"] == c["reduced"]
        assert all(k in cfg for k in c["reduced"])


def test_a_new_config_mix_cell_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = _json(ROOT / "BENCHMARK.json")
    b = root / "bench"
    shutil.copy(OPS10, b / "configs" / "ops10b.json")
    (b / "traffic" / "sweep_block256.json").write_text(json.dumps(
        {"kind": "sweep", "block": 256, "blocks": 2}))
    (b / "cells" / "ops10b-sweep256.json").write_text(json.dumps(
        {"sample_rows": 64, "limits": {"cycles_err_max": 0.1}}))
    (b / "metrics" / "calls_total.py").write_text(
        "def read(run):\n    return float(run['calls'])\n")
    m["configs"].append({"name": "ops10b", "source": "https://example.org",
                         "file": "bench/configs/ops10b.json",
                         "reduced": [], "why": "a copy"})
    m["workloads"].append({"name": "ops10b-sweep256", "config": "ops10b",
                           "traffic": "sweep_block256", "chips": 1,
                           "why": "a new cell"})
    m["per_layer"].append({"name": "calls_total", "unit": "calls",
                           "better": "higher", "source": "host_clock",
                           "layer": "device", "moves": "configs_per_s",
                           "workloads": ["ops10b-sweep256"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    cell, man, config, traffic, check = bench.load_cell("ops10b-sweep256",
                                                        root=root)
    assert config["name"] == "ops10" and traffic["block"] == 256
    assert check["sample_rows"] == 64
    assert bench.metric_names(man, "ops10b-sweep256", "per_layer") == [
        "calls_total"]
    assert bench.read_metric("calls_total", {"calls": 3},
                             bench=b) == 3.0


# -- whole runs on the CPU -----------------------------------------------------


TWIN = {"net28-sweep": "ops10-sweep", "net28-serve": "ops10-serve"}


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A checkout whose manifest also holds ``ops10`` twins of the cells,
    added as files and entries: the CPU drives them at a test's cost."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = root / "bench"
    shutil.copy(OPS10, b / "configs" / "ops10.json")
    for net, ops in TWIN.items():
        shutil.copy(b / "cells" / f"{net}.json", b / "cells" / f"{ops}.json")
    shutil.copy(b / "traffic" / "zipf_serve_net28.json",
                b / "traffic" / "zipf_serve_ops10.json")
    m = _json(ROOT / "BENCHMARK.json")
    # the serve cell's entries, kept out of the manifest until the cell is
    # proven on the chip
    for key, entries in _json(DATA / "net28_serve_entries.json").items():
        have = {e["name"] for e in m[key]}
        m[key] += [e for e in entries if e["name"] not in have]
    if "ops10" not in {c["name"] for c in m["configs"]}:
        m["configs"].append({"name": "ops10", "file": "bench/configs/"
                             "ops10.json", "reduced": [],
                             "source": "https://arxiv.org/abs/2402.00069",
                             "why": "operator matrix"})
    have = {w["name"] for w in m["workloads"]}
    for w in list(m["workloads"]):
        if w["name"] in TWIN and TWIN[w["name"]] not in have:
            m["workloads"].append(dict(w, name=TWIN[w["name"]],
                                       config="ops10", traffic=w["traffic"]
                                       .replace("net28", "ops10")))
    for e in m["end_to_end"] + m["per_layer"]:
        if "workloads" in e:
            e["workloads"] += [TWIN[w] for w in e["workloads"]
                               if w in TWIN and TWIN[w] not in e["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root


def _drive(root, workload, seed=5, seconds=1.0, trace=0):
    """A whole run with the look for a chip skipped; returns the parsed
    last line of standard output."""
    out = io.StringIO()
    ns = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                            trace=trace)
    with contextlib.redirect_stdout(out):
        rc = bench.run(ns, require_tpu=False, root=root)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_last_line_has_the_contract_keys_and_checks_come_last(
        small_root):
    res = _drive(small_root, "ops10-sweep")
    assert set(res) == CONTRACT_KEYS | {"checks"}
    assert list(res)[-1] == "checks"
    assert set(res["device"]) == DEVICE_KEYS
    assert set(res["metrics"]) == {"setup_s", "configs_per_s"}
    for v in res["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] > 0
    for v in res["checks"].values():
        assert set(v) == {"value", "limit"}
    assert res["correct"] is True and res["attempted"] > 0


def test_a_traced_run_reports_per_layer_metrics_and_a_breakdown(
        small_root):
    res = _drive(small_root, "ops10-sweep", trace=1)
    assert set(res) == CONTRACT_KEYS | {"checks", "breakdown"}
    assert set(res["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device plane: the device's metrics stay silent
    assert set(res["metrics"]) == {"packed_scan_steps"}


def test_run_exits_non_zero_and_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"),
                        "--workload", "net28-sweep", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_run_fails_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "net28-sweep", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout


# -- the control and planted faults read as not correct ------------------------


@pytest.fixture(scope="module")
def ops10_reference():
    from export import plain_cells
    from reference import Reference

    config = _json(OPS10)
    ex = bench.build_explorer(config)
    cells = plain_cells(ex)
    return (config, Reference(cells, config),
            Reference(cells, config, dtype=control.low_precision()))


def test_the_lower_precision_control_reads_not_correct(ops10_reference):
    config, ref, low = ops10_reference
    sweep = _json(BENCH / "traffic" / "sweep_block1024.json")
    check = dict(_json(BENCH / "cells" / "net28-sweep.json"),
                 sample_rows=48)
    nums = control.control_sweep(ref, low, config, dict(sweep, blocks=1),
                                 check, seed=9)
    lim = {k: v for k, v in check["limits"].items() if k in nums}
    assert not correct.judge(nums, lim)
    serve = _json(BENCH / "traffic" / "zipf_serve_net28.json")
    check = dict(_json(BENCH / "cells" / "net28-serve.json"),
                 sample_blocks=1)
    nums = control.control_serve(ref, low, config, serve, check, seed=9,
                                 seconds=5.0)
    lim = {k: v for k, v in check["limits"].items() if k in nums}
    assert not correct.judge(nums, lim)


def test_pins_off_theta_one_catch_a_node_moved_to_another_knob(
        ops10_reference):
    """A graph whose op class moves from the matrix knob to the vector
    knob keeps its cycles at theta = 1; the pins off theta = 1 see it."""
    import copy

    from reference import Reference

    config, ref, _ = ops10_reference
    assert bench.pin_numbers(ref, config)["pin_err_max"] <= 1e-9
    cells = copy.deepcopy(ref.cells)
    g = cells[0].graphs[0]
    k = next(i for i, nm in enumerate(g.class_names)
             if re.search(config["knobs"][0]["ops"], nm)
             and (g.op_class == i).any())
    g.class_names[k] = "attn@moved"
    moved = Reference(cells, config)
    one = np.ones((1, moved.K), np.float32)
    assert np.array_equal(moved.evaluate(one)[0], ref.evaluate(one)[0])
    assert bench.pin_numbers(moved, config)["pin_err_max"] > 1e-3


def _alter_one_cell(c, e):
    c = np.array(c, copy=True)
    c[:, 3] *= 1.05
    return c, e


def _drop_half(c, e):
    h = c.shape[0] // 2
    c, e = np.array(c, copy=True), np.array(e, copy=True)
    c[h:2 * h], e[h:2 * h] = c[:h], e[:h]
    return c, e


@pytest.mark.parametrize("fault", [_alter_one_cell, _drop_half],
                         ids=["answer-altered", "half-the-batch-left-out"])
def test_a_sweep_with_a_broken_timed_path_reads_not_correct(monkeypatch,
                                                            small_root,
                                                            fault):
    from repro.core.aidg.dse import PackedMatrix

    real = PackedMatrix.evaluate_full

    def broken(self, *a, **k):
        return fault(*real(self, *a, **k))

    monkeypatch.setattr(PackedMatrix, "evaluate_full", broken)
    res = _drive(small_root, "ops10-sweep")
    assert res["correct"] is False


def test_a_served_answer_altered_where_it_is_produced_reads_not_correct(
        monkeypatch, small_root):
    from repro.serve.engine import DSEService
    from repro.serve.query import Answer

    real = DSEService._rank

    def broken(self, *a, **k):
        ans = real(self, *a, **k)
        return Answer(ans.query, ans.cells, tuple(reversed(ans.designs)),
                      ans.best_arch, tier=ans.tier)

    monkeypatch.setattr(DSEService, "_rank", broken)
    res = _drive(small_root, "ops10-serve", seconds=2.0)
    assert res["correct"] is False
