"""Share of the traced window in which no operation ran on the device:
1 - union of busy intervals / window, averaged over chips.  Moves
``tokens_per_s``."""


def read(rec):
    tr = rec["trace"]
    if tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
