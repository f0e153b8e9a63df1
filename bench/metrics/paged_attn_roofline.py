"""Kernel: the paged decode kernel's least time over its device time,
in %.  The least time is the larger of its FLOPs over peak FLOP/s and
its HBM bytes over peak bandwidth (``bench/flops.paged_kernel_cost``,
rows that decode a token only), summed over the traced decode steps;
the device time sums the kernel's events in the trace over the chips
that ran them (each chip of a data mesh runs its own share of the
rows)."""

import sys

from bench import flops


def read(run):
    tr = run.trace
    steps = [s for s in run.window.steps if s.traced and s.decode]
    if tr is None or not steps:
        return None
    kernel_s = tr.kernel_seconds("paged_attention") * tr.devices
    if kernel_s <= 0:
        return None
    fl = by = 0
    for s in steps:
        f, b = flops.paged_kernel_cost(run.conf, s.decode, run.server["block_size"])
        fl, by = fl + f, by + b
    t_flops, t_bytes = fl / run.peaks.flops, by / run.peaks.hbm_bytes_s
    bound = "HBM bandwidth" if t_bytes >= t_flops else "compute"
    print(f"[bench] paged_attn_roofline: {fl} FLOPs, {by} bytes over {len(steps)} decode steps, bound by {bound}; kernel device time {kernel_s:.6f} s over {tr.devices} chip(s)", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / kernel_s
