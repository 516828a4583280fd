"""Tests of the reduction of the program's named scopes and spans
(``program_trace.py``) and of the metrics that read it.  CPU only.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import profile_trace as tr  # noqa: E402
import program_trace as pt  # noqa: E402
import run as bench  # noqa: E402

SCOPED = BENCH / "tests" / "data" / "trace_net28_sweep_scoped.json"
NEW = ("bucket_scan_ms_per_call", "slowest_bucket_ms_per_call",
       "explore_host_ms_per_call", "packed_pad_efficiency")


def _reduced(devices, op_scopes, spans, lo, hi):
    scopes = [pt.UNSCOPED] + sorted(set(op_scopes.values()))
    arrays = {k: pt.device_ops(v, op_scopes, scopes)
              for k, v in devices.items()}
    return pt.reduce(arrays, scopes, spans, lo, hi)


def test_a_loop_and_the_ops_nested_in_it_count_once_for_its_scope():
    # a while loop of bucket00 with an unscoped and a bucket00 op nested
    # in it, a bucket01 op, the composition, an unscoped op after it
    devices = {"/device:TPU:0": [
        ("%while.1 = (f32[8]) while(...)", 0.0, 100.0),
        ("%dynamic-update-slice.2 = f32[8] dynamic-update-slice(...)",
         10.0, 40.0),
        ("%fusion.3 = f32[8] fusion(...)", 50.0, 60.0),
        ("%while.4 = (f32[8]) while(...)", 120.0, 150.0),
        ("%fusion.5 = f32[8] fusion(...)", 150.0, 155.0),
        ("%copy.6 = f32[8] copy(...)", 160.0, 170.0)]}
    op_scopes = {"while.1": "packed.bucket00", "fusion.3": "packed.bucket00",
                 "while.4": "packed.bucket01", "fusion.5": "packed.compose"}
    r = _reduced(devices, op_scopes, [], 0.0, 200.0)
    assert r["scopes"] == {"packed.bucket00": pytest.approx(100e-9),
                           "packed.bucket01": pytest.approx(30e-9),
                           "packed.compose": pytest.approx(5e-9)}
    # busy time is the harness's own reading of the same operations
    s = tr.summarize({k: list(v) for k, v in devices.items()}, [], 0.0,
                     200.0)
    assert r["busy_s"] == pytest.approx(s["busy_s"]) == pytest.approx(
        145e-9)
    # a stretch that starts inside the loop clips it
    r = _reduced(devices, op_scopes, [], 55.0, 200.0)
    assert r["scopes"]["packed.bucket00"] == pytest.approx(45e-9)


def test_a_span_s_self_time_leaves_out_its_children_on_the_same_thread():
    spans = [("explore.call", "python3", 0.0, 100.0),
             ("explore.evaluate", "python3", 0.0, 80.0),
             ("packed.wait", "python3", 10.0, 70.0),
             ("serve.window", "worker", 20.0, 30.0)]
    s = pt.span_seconds(spans, 5.0, 95.0)
    got = {k: (v["total_s"] * 1e9, v["self_s"] * 1e9) for k, v in s.items()}
    assert got == {"explore.call": pytest.approx((90.0, 15.0)),
                   "explore.evaluate": pytest.approx((75.0, 15.0)),
                   "packed.wait": pytest.approx((60.0, 60.0)),
                   "serve.window": pytest.approx((10.0, 10.0))}


def _fixture():
    return json.loads(SCOPED.read_text())


def _sweep_run(rec, trace):
    return {"kind": "sweep", "calls": 30, "window_s": 30.0,
            "calls_in_stretch": rec["calls_in_stretch"],
            "packed_stats": rec["packed_stats"], "trace": trace}


def test_the_recorded_scoped_trace_reduces_to_its_brute_force_values():
    rec = _fixture()
    r = _reduced(rec["devices"], rec["op_scopes"],
                 [tuple(s) for s in rec["spans"]], rec["lo"], rec["hi"])
    want = rec["expected"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["scopes"] == {k: pytest.approx(v, rel=1e-9)
                           for k, v in want["scopes"].items()}
    # every bucket of the packed matrix has a scope, and the scopes cover
    # almost all of the device's busy time
    assert sum(k.startswith("packed.bucket") for k in r["scopes"]) == \
        rec["packed_stats"]["buckets"]
    assert sum(r["scopes"].values()) > 0.95 * r["busy_s"]


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_reads_its_known_value(monkeypatch, name):
    rec = _fixture()
    r = _reduced(rec["devices"], rec["op_scopes"],
                 [tuple(s) for s in rec["spans"]], rec["lo"], rec["hi"])
    monkeypatch.setattr(pt, "of", lambda run: r)
    trace = {"window_s": (rec["hi"] - rec["lo"]) * 1e-9,
             "busy_s": r["busy_s"]}
    got = bench.read_metric(name, _sweep_run(rec, trace))
    assert got == pytest.approx(rec["expected"][name], rel=1e-9)
    serve = {"kind": "serve", "pool": 256, "records": [],
             "stats_window": ({}, {}), "stats_stretch": ({}, {}),
             "trace": trace}
    assert bench.read_metric(name, serve) is None


def test_a_program_without_scopes_or_spans_reads_none(monkeypatch):
    rec = _fixture()
    r = _reduced(rec["devices"], {}, [], rec["lo"], rec["hi"])
    assert r["scopes"] == {} and r["spans"] == {}
    monkeypatch.setattr(pt, "of", lambda run: r)
    trace = {"window_s": 1.0, "busy_s": r["busy_s"]}
    stats = {k: v for k, v in rec["packed_stats"].items()
             if k != "pad_efficiency"}
    run = dict(_sweep_run(rec, trace), packed_stats=stats)
    assert [bench.read_metric(n, run) for n in NEW] == [None] * len(NEW)


def test_the_readers_find_the_profile_the_harness_traced(monkeypatch,
                                                         tmp_path):
    """The harness's own tracer, on the CPU: the profile is found under
    the temp directory by the summary it gave, and a summary of another
    profile finds nothing."""
    import jax
    from repro.tracing import span

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    tracer = bench.Tracer(True)
    try:
        tracer.start()
        for _ in range(2):
            with span("explore.call"):
                with span("packed.wait"):
                    jax.block_until_ready(jax.numpy.ones(8) + 1)
                time.sleep(0.002)
        tracer.stop()
        summary = tracer.summary()
        run = {"kind": "sweep", "calls_in_stretch": 2,
               "packed_stats": {}, "trace": summary}
        r = pt.of(run)
        assert r is not None and r["scopes"] == {}
        assert set(r["spans"]) == {"explore.call", "packed.wait"}
        host = bench.read_metric("explore_host_ms_per_call", run)
        assert 2.0 <= host < 1e3          # the 2 ms sleep, per call
        other = dict(run, trace=dict(summary, busy_s=summary["busy_s"] + 1))
        assert pt.of(other) is None
    finally:
        tracer.cleanup()
    assert pt.of(run) is None             # no profile left
