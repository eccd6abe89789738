"""Median, over the engine's ``serve.step`` spans in the traced window
that hold a ``serve.decode`` and no ``serve.admit``, of the time inside
the step in which no operation ran on the first TPU device."""
from bench import spans


def value(run):
    busy = spans.chip_busy(run)
    if busy is None:
        return None
    return spans.median_ms(spans.decode_step_idle_ns(
        spans.window_spans(run), busy))
