"""Median, over the engine's ``serve.prefill`` spans in the traced
window, of the device time of the prefill program (``jit_prefill_impl``)
inside the span: the batch-1 prefill of one prompt on the chip."""
from bench import spans


def value(run):
    runs = spans.prefill_runs(run)
    if runs is None:
        return None
    return spans.median_ms(spans.prefill_device_ns(spans.window_spans(run),
                                                   runs))
