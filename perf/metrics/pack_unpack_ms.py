"""Device time of the level-buffer packing (scope ``gc_pack``: each
leaf's combined gradient flattened into its level's buffer) and unpacking (scope
``gc_unpack``: the decoded buffer back into leaves), per step and chip,
by ``perf/scopes.py``.  Moves ``tokens_per_s``."""
from perf import scopes


def read(rec):
    return scopes.per_step_ms(rec, "gc_pack", "gc_unpack")
