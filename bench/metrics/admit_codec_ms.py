"""Median, over the engine's ``serve.admit`` spans in the traced
window, of the time its ``serve.kv.extract`` and ``serve.kv.insert``
spans take: the prefill cache through the host byte image into the
decode slot."""
from bench import spans


def value(run):
    return spans.median_ms(spans.admit_codec_ns(spans.window_spans(run)))
