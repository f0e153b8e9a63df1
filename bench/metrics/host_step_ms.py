"""Server host loop: median host time of a traced ``step_once``, in ms:
each ``serve.step`` span's duration less its ``serve.wait`` children
(the host's one wait on the device), from the server's spans in the
device trace.  The profiler's Python tracer slows the host, so this
reads higher than an untraced step's host time."""

from bench.metrics._common import percentile
from bench.metrics._spans import spans


def read(run):
    sp = spans(run)
    if sp is None:
        return None
    return percentile([(b - a - sum(wb - wa for _, wa, wb, _ in sp.inside("serve.wait", a, b))) / 1e6 for _, a, b, _ in sp.named("serve.step")], 50)
