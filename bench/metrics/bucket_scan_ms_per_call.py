"""Packed evaluator, bucket scans: device ms per ``Explorer.explore`` call
in the traced stretch under the ``packed.bucketNN`` named scopes, summed
over the buckets (each scope the union of its operations' intervals)."""

import program_trace


def read(run):
    if run.get("kind") != "sweep":
        return None
    pt = program_trace.of(run)
    calls = run["calls_in_stretch"]
    if pt is None or calls <= 0:
        return None
    scans = [v for k, v in pt["scopes"].items()
             if k.startswith("packed.bucket")]
    return sum(scans) / calls * 1e3 if scans else None
