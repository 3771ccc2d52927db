"""Requests the one dispatch thread completes per second of its busy time:
the requests of every steady ``serve.dispatch`` span (a bucket in which JAX
compiled nothing) over those spans' time.  Read from the program's span
record (``repro.obs.SPANS``) over the whole run; silent where the program
has no such spans."""

from repro import obs

ROOT = "serve.dispatch"


def read(run):
    spans = getattr(obs, "SPANS", None)
    if spans is None:
        return None
    snap = spans.snapshot()
    total = snap["histograms"].get(ROOT, {}).get("sum")
    requests = snap["counters"].get(f"{ROOT}.items", 0)
    if not total or not requests:
        return None
    return requests / total
