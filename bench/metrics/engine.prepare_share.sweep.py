"""Share of the sweep calls' time spent before the device has the call:
building the host inputs (``engine.prepare``, the per-seed key loop
included) and enqueueing the program (``engine.launch``), over the time of
every steady ``engine.run_sweep`` call.  Read from the program's span
record (``repro.obs.SPANS``) over the whole run; silent where the program
has no such spans."""

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
    before = sum(h.get(f"{ROOT}/{child}", {}).get("sum", 0.0)
                 for child in ("engine.prepare", "engine.launch"))
    return 100.0 * before / total
