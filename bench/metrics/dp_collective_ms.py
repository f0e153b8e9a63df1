"""DP sharding of the paged step: median, over the traced decode steps
(``serve.step`` spans that hold a ``serve.decode``), of the device time
of collective ops inside the step, averaged over the chips of the run,
in ms.  A collective op is an HLO all-gather, all-reduce,
reduce-scatter, collective-permute or all-to-all, or the ``-start`` and
``-done`` halves of an asynchronous one; a chip's time is the union of
their intervals.  None where the trace holds no collective op (one
chip)."""

import re

from bench import trace_reduce as tr
from bench.metrics._common import percentile
from bench.metrics._spans import spans

COLLECTIVE = re.compile(r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)(-start|-done)?([.-]|$)")


def read(run):
    sp = spans(run)
    if sp is None:
        return None
    chips = [[(a, b) for n, a, b in ops if COLLECTIVE.match(n)] for ops in sp.chip_ops[: run.trace.devices]]
    if not any(chips):
        return None
    steps = [(a, b) for _, a, b, _ in sp.named("serve.step") if sp.inside("serve.decode", a, b)]

    def chip_ns(ivs, a, b):
        inside = [c for x, y in ivs if (c := tr.clip(x, y, a, b))]
        return sum(y - x for x, y in tr.union(inside))

    return percentile([sum(chip_ns(ivs, a, b) for ivs in chips) / len(chips) / 1e6 for a, b in steps], 50)
