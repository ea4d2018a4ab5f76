"""Share of the traced grid pass in which no op ran on the chip, in %."""
from bench.layers import idle_pct


def read(ctx):
    return idle_pct(ctx)
