"""Service and micro-batcher: answered queries per pool-sized device
dispatch over the window (the service's dispatched candidates divided by
the pool; every dispatch is padded to one pool)."""


def read(run):
    if run.get("kind") != "serve":
        return None
    st0, st1 = run["stats_window"]
    dispatches = (st1["dispatched_candidates"]
                  - st0["dispatched_candidates"]) / run["pool"]
    answered = sum(1 for r in run["records"] if r.ok)
    return answered / dispatches if dispatches > 0 else None
