"""Packed evaluator bucketing: the share, in %, of the padded work of one
dispatch that is real, by the bucketing cost model (``PackedMatrix
.stats()["pad_efficiency"]``, summed per-row cost over padded bucket cost;
an exact count)."""


def read(run):
    if run.get("kind") != "sweep":
        return None
    eff = run["packed_stats"].get("pad_efficiency")
    return None if eff is None else 100.0 * eff
