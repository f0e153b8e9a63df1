"""Server host loop: median device idle time inside a traced
``step_once``, in ms: each ``serve.step`` span less the union of the
chip's ``XLA Ops`` inside it.  Also logs the traced window's idle
seconds by the innermost ``serve.*`` span at each gap's middle
(``harness`` where no ``serve.step`` is open)."""

import sys

from bench.metrics._common import percentile
from bench.metrics._spans import spans


def read(run):
    sp = spans(run)
    if sp is None:
        return None
    by = sorted(sp.idle_by_span().items(), key=lambda kv: -kv[1])
    print("[bench] host_idle_ms: idle s by span over the traced window: " + ", ".join(f"{k} {v:.9f}" for k, v in by), file=sys.stderr)
    return percentile([(b - a - sp.busy_ns(a, b)) / 1e6 for _, a, b, _ in sp.named("serve.step")], 50)
