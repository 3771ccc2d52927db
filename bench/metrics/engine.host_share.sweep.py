"""Share of the sweep calls' time in which the host, not the device, held
them up: the time of every steady ``engine.run_sweep`` call (one in which
JAX compiled nothing) less its ``engine.device_wait``, over the calls' time.
Read from the program's span record (``repro.obs.SPANS``) over the whole
run, the traced part included; silent where the program has no such
spans."""

from repro import obs

ROOT = "engine.run_sweep"


def read(run):
    spans = getattr(obs, "SPANS", None)
    if spans is None:
        return None
    h = spans.snapshot()["histograms"]
    total = h.get(ROOT, {}).get("sum")
    if not total:
        return None
    wait = h.get(f"{ROOT}/engine.device_wait", {}).get("sum", 0.0)
    return 100.0 * (total - wait) / total
