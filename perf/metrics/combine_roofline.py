"""Fused combine against the HBM roofline: it streams the (K, P) float32
shard-gradient stack once and writes P floats (``perf/flops.py``), so
bandwidth bounds it.  Least time over kernel time.  Moves
``tokens_per_s``."""


def read(rec):
    tr = rec["trace"]
    sec = tr["class_s"].get("combine", 0.0)
    if tr["steps"] == 0 or sec <= 0:
        return None
    least = rec["combine_bytes"] / rec["chips"] / rec["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / (sec / tr["steps"])
