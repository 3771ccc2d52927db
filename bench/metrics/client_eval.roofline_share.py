"""The ``client_eval`` kernel's share of its roofline: the least time the
chip could take for the traced lane-rounds' client evaluations (the larger
of their operations over the peak FLOP/s and their bytes over the HBM
bandwidth, ``bench/roofline.py``), over the kernel's device time in the
trace, summed over the chips."""

from bench import roofline


def read(run):
    t, s = run["trace"], run["stats"]
    if not t or not t["kernel_launches"] or not t["kernel_s"]:
        return None
    ops, nbytes = roofline.client_eval_work(s["K"], s["window"],
                                            with_grad=s["with_grad"])
    lane_rounds = s["traced_scan_rounds"] * s["lanes_per_call"]
    least, _ = roofline.least_time_s(ops * lane_rounds, nbytes * lane_rounds,
                                     roofline.peaks(run["device_kind"]))
    return 100.0 * least / t["kernel_s"]
