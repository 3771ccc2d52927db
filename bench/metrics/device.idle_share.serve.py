"""Share of the traced window in which no operation ran on the device
(1 - the union of the operation intervals / the window), averaged over
the chips the cell uses."""


def read(run):
    t = run["trace"]
    if not t or not t["window_s"] or not t["chips_traced"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
