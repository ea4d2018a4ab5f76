"""Programs the process compiled or loaded from the persistent cache up
to the end of the traced grid pass, warm-up included, as the program's
own counter (``repro.obs.counters()``) has them."""
from bench import spans


def read(ctx):
    return spans.programs()
