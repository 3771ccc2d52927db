"""Share of the device's busy time spent in the ``client_eval`` kernel,
over the traced window and the chips the cell uses."""


def read(run):
    t = run["trace"]
    if not t or not t["kernel_launches"] or not t["busy_s"]:
        return None
    return 100.0 * t["kernel_s"] / (t["busy_s"] * t["chips_traced"])
