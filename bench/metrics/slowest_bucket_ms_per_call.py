"""Packed evaluator, costliest bucket: device ms per ``Explorer.explore``
call in the traced stretch under the one ``packed.bucketNN`` named scope
that takes the most (``PackedMatrix.stats()["bucket_detail"]`` gives its
shapes and cells)."""

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
    return max(scans) / calls * 1e3 if scans else None
