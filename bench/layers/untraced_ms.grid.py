"""Host ms of the traced grid pass that none of the sweep's five leaf
spans covers: the traced window less ``sweep.prepare``, ``launch``,
``wait``, ``fetch`` and ``postprocess``."""
from bench import spans


def read(ctx):
    return spans.untraced_ms(spans.records(), ctx["trace"]["window_s"],
                             ctx["counters"]["units"])
