"""Host ms a grid pass spends in the program's ``sweep.launch``
spans (``repro.api.scenario``)."""
from bench import spans


def read(ctx):
    return spans.leaf_ms(spans.records(), "sweep.launch",
                         ctx["counters"]["units"])
