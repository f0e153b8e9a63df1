"""Server step: median host time of a ``step_once`` that ran a prefill
chunk and a decode, over the window's steps."""

from bench.metrics._common import percentile, step_ms


def read(run):
    return percentile(step_ms(run, "mixed"), 50)
