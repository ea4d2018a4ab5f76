"""Device busy time per grid pass, in ms (the engine runners' work on
the chip)."""
from bench.layers import per_unit_ms


def read(ctx):
    return per_unit_ms(ctx)
