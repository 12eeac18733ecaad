"""Scheduler (core/scheduler.py): mean milliseconds from a request's
submission to its first admission, over the requests first admitted in
the window (``queue_wait_s`` / ``queue_waits``).  None where no request
was admitted in the window, or the program does not count waits."""


def read(run):
    a, b = run.stats_open["scheduler"], run.stats_close["scheduler"]
    if "queue_waits" not in a:
        return None
    n = b["queue_waits"] - a["queue_waits"]
    if n <= 0:
        return None
    return 1000 * (b["queue_wait_s"] - a["queue_wait_s"]) / n
