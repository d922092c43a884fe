"""Device time of the optimizer (scope ``optimizer``: the learning-rate
schedule, the global-norm clip and the AdamW update), per step and chip, by
``perf/scopes.py``.  Moves ``tokens_per_s``."""
from perf import scopes


def read(rec):
    return scopes.per_step_ms(rec, "optimizer")
