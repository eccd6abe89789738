"""Median, over the engine's ``serve.admit`` spans in the traced
window, of the time inside the span in which no operation ran on the
first TPU device."""
from bench import spans


def value(run):
    busy = spans.chip_busy(run)
    if busy is None:
        return None
    return spans.median_ms(spans.admit_idle_ns(spans.window_spans(run),
                                               busy))
