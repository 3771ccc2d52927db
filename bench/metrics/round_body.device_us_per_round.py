"""Device time per simulated round of the sweep program: the busy time of
the chips in the traced calls (averaged over the chips) over the scan
rounds those calls advanced.  A round here is one step of the scan for all
lanes of the grid at once, not one lane's round."""


def read(run):
    t, s = run["trace"], run["stats"]
    if not t or not t["busy_s"] or not s.get("traced_scan_rounds"):
        return None
    return 1e6 * t["busy_s"] / s["traced_scan_rounds"]
