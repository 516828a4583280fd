"""Explorer: host ms per ``Explorer.explore`` call in the traced stretch
outside the wait for the device: the program's ``explore.call`` span less
its ``packed.wait`` spans (upload and launch, fetch, scoring and the
Pareto front)."""

import program_trace


def read(run):
    if run.get("kind") != "sweep":
        return None
    pt = program_trace.of(run)
    calls = run["calls_in_stretch"]
    if pt is None or calls <= 0:
        return None
    spans = pt["spans"]
    if "explore.call" not in spans or "packed.wait" not in spans:
        return None
    host = spans["explore.call"]["total_s"] - spans["packed.wait"]["total_s"]
    return host / calls * 1e3
