"""Share of the batched lanes the server dispatched that carried a request
(the rest pad a bucket to its width), over the window: from the server's
``server.batched_lanes`` and ``server.padded_lanes`` counters, before and
after.  ``batched_lanes`` counts every dispatched lane, padding included."""


def read(run):
    s = run["stats"]
    if not s.get("batched_lanes"):
        return None
    return 100.0 * (s["batched_lanes"] - s["padded_lanes"]) \
        / s["batched_lanes"]
