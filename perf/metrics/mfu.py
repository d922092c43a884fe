"""Whole coded step's share of the chip's bf16 peak (useful FLOPs only).

Useful FLOPs are one unique shard per rank per step (``perf/flops.py``);
the K-fold redundant passes do not count.  Moves ``tokens_per_s``.
"""


def read(rec):
    tr = rec["trace"]
    if tr["steps"] == 0 or tr["window_s"] <= 0:
        return None
    flops = rec["step_flops"]["useful"] * tr["steps"]
    return 100.0 * flops / tr["window_s"] / rec["chips"] / rec["peak"][
        "bf16_flops"]
