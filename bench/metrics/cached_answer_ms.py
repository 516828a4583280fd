"""Query front end: median client latency, in ms, of the window's answers
that came from the service's answer cache (each timed from when it was
due to the decoded answer, on the client's clock)."""

import numpy as np


def read(run):
    if run.get("kind") != "serve":
        return None
    lat = [r.done - r.due for r in run["records"]
           if r.ok and r.answer.cached]
    return float(np.median(lat) * 1e3) if lat else None
