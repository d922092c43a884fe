"""Device idle time per step in the step loop, averaged over chips; the
result line's ``breakdown.idle_gaps`` attributes it to the host spans
running in it.  Moves ``tokens_per_s``."""


def read(rec):
    tr = rec["trace"]
    if tr["steps"] == 0:
        return None
    return 1e3 * (tr["window_s"] - tr["busy_s"]) / tr["steps"]
