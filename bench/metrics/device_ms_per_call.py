"""Packed evaluator on the device: device busy ms in the traced stretch per
``Explorer.explore`` call made in it."""


def read(run):
    if run.get("kind") != "sweep":
        return None
    calls = run["calls_in_stretch"]
    busy = run["trace"]["busy_s"]
    return busy / calls * 1e3 if calls > 0 and busy > 0 else None
