"""Level collectives' time not overlapped by compute on the same chip,
per step (``perf/trace_reduce.py``).  Moves ``tokens_per_s``."""


def read(rec):
    tr = rec["trace"]
    if tr["steps"] == 0 or tr["class_s"].get("collective", 0.0) <= 0:
        return None
    return 1e3 * tr["collective_exposed_s"] / tr["steps"]
