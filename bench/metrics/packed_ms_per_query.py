"""Exact tier: host-clock ms per fresh query that the packed tier answered
in the window (dispatch, fetch and ranking; the service's own counters)."""


def read(run):
    if run.get("kind") != "serve":
        return None
    st0, st1 = run["stats_window"]
    n = st1["tiers"]["packed"] - st0["tiers"]["packed"]
    t = st1["tier_time_s"]["packed"] - st0["tier_time_s"]["packed"]
    return t / n * 1e3 if n > 0 else None
