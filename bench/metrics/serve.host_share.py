"""Share of the dispatch thread's bucket time in which the host, not the
device, held the bucket up: the time of every steady ``serve.dispatch``
span (a bucket in which JAX compiled nothing) less the ``engine.device_wait``
of its ``run_batch``, over the buckets' time.  Read from the program's
span record (``repro.obs.SPANS``) over the whole run, the traced part
included; silent where the program has no such spans."""

from repro import obs

ROOT = "serve.dispatch"


def read(run):
    spans = getattr(obs, "SPANS", None)
    if spans is None:
        return None
    h = spans.snapshot()["histograms"]
    total = h.get(ROOT, {}).get("sum")
    if not total:
        return None
    wait = h.get(f"{ROOT}/engine.run_batch/engine.device_wait",
                 {}).get("sum", 0.0)
    return 100.0 * (total - wait) / total
