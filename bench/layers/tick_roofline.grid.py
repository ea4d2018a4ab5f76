"""Tick bytes of the traced grid pass against the HBM roofline, in %."""
from bench.layers import tick_roofline_pct


def read(ctx):
    return tick_roofline_pct(ctx)
