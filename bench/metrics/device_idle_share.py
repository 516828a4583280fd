"""Device: the share, in %, of the traced stretch in which no operation ran
on the device (1 - busy / stretch).  It reads ``device_idle_share.serve``
and ``device_idle_share.sweep``, the quantity split by the end-to-end
metric it moves."""


def read(run):
    t = run["trace"]
    if t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
