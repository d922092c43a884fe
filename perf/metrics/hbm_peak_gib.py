"""Device memory the compiled step holds on the fullest chip: its
arguments, unaliased outputs, temporaries and code, from the compiler's
``memory_analysis``, in GiB.  Moves ``tokens_per_s``: what does not fit
caps the batch."""


def read(rec):
    held = rec.get("compiled_bytes") or 0
    return held / 2**30 if held > 0 else None
