"""Median duration of the engine's ``serve.sample`` spans outside any
``serve.admit`` in the traced window: the host sampling of one decode
step's tokens."""
from bench import spans


def value(run):
    return spans.median_ms(spans.decode_sample_ns(spans.window_spans(run)))
