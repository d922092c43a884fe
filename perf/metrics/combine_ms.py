"""Device time of the fused encode-decode combine (the Pallas kernels),
per step and chip.  Moves ``tokens_per_s``."""


def read(rec):
    tr = rec["trace"]
    sec = tr["class_s"].get("combine", 0.0)
    if tr["steps"] == 0 or sec <= 0:
        return None
    return 1e3 * sec / tr["steps"]
