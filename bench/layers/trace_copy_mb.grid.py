"""MB of per-tick traces a grid pass returns to the host: ``nbytes`` of
every leaf of each TransferResult's ``metrics`` (the api layer's copy)."""


def read(ctx):
    n = ctx["counters"].get("trace_copy_bytes")
    return n / 1e6 if n else None
