"""Per-shard forward+backward passes against the bf16 FLOP roofline.

FLOPs of the K shards' passes per step (``perf/flops.py``) over peak
times the device time of the instructions under ``jvp(``, per step and
chip (``perf/trace_reduce.py``).  Moves ``tokens_per_s``.
"""


def read(rec):
    tr = rec["trace"]
    sec = tr["class_s"].get("backward", 0.0)
    if tr["steps"] == 0 or sec <= 0:
        return None
    per_chip = rec["step_flops"]["backward"] / rec["chips"]
    return 100.0 * per_chip / (rec["peak"]["bf16_flops"] * sec / tr["steps"])
