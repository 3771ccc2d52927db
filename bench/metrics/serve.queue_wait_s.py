"""Mean time a request waited in the server's queue in the window: the
change in the ``server.queue.wait_s`` histogram's sum over the change in
its count."""


def read(run):
    s = run["stats"]
    if not s.get("queue_wait_count"):
        return None
    return s["queue_wait_sum"] / s["queue_wait_count"]
