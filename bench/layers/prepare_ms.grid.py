"""Host ms a grid pass spends in the program's ``sweep.prepare``
spans (``repro.api.scenario``)."""
from bench import spans


def read(ctx):
    return spans.leaf_ms(spans.records(), "sweep.prepare",
                         ctx["counters"]["units"])
