"""Whole step: model FLOPs of every prefill and decode token computed
in the traced steps (``bench/flops.py``), over the traced window times
the chip's peak, in %."""

from bench import flops


def read(run):
    tr = run.trace
    steps = [s for s in run.window.steps if s.traced]
    if tr is None or not steps or tr.window_s <= 0:
        return None
    total = sum(flops.step_flops(run.conf, s.prefill, s.decode) for s in steps)
    return 100.0 * total / (tr.window_s * run.peaks.flops * tr.devices)
