"""Packed evaluator on the device: device busy ms in the traced stretch per
pool-sized dispatch in it (the service's dispatched candidates over the
stretch, divided by the pool)."""


def read(run):
    if run.get("kind") != "serve" or None in run["stats_stretch"]:
        return None
    sa, sb = run["stats_stretch"]
    dispatches = (sb["dispatched_candidates"]
                  - sa["dispatched_candidates"]) / run["pool"]
    busy = run["trace"]["busy_s"]
    return busy / dispatches * 1e3 if dispatches > 0 and busy > 0 else None
