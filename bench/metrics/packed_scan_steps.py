"""Packed evaluator bucketing: the padded sequential scan steps of one
dispatch, summed over the shape buckets (``PackedMatrix.stats()``; an
exact count)."""


def read(run):
    if run.get("kind") != "sweep":
        return None
    return float(run["packed_stats"]["scan_len"])
