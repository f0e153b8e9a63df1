"""Server step: median host time of a decode-only ``step_once``, over
the window's steps."""

from bench.metrics._common import percentile, step_ms


def read(run):
    return percentile(step_ms(run, "decode"), 50)
