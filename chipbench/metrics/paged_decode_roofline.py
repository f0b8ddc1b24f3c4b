"""Kernels: the paged decode kernel's share of its roofline in the
traced part of the window, in %. The numerator is the least time the
chip could take to read each decoded token's cached context, from the
tokens received in the traced interval (``chipbench/flops.py``); the
denominator is the device time of the kernel's events. Moves
``output_tok_s``."""
from chipbench.flops import paged_decode_work, roofline_seconds

KERNEL = r"paged_decode|_paged_kernel"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    t = run.trace.op_seconds(KERNEL)
    ctx = run.decoded_in(run.trace_t0, run.trace_t1)
    if t <= 0 or not ctx:
        return None
    least = sum(roofline_seconds(*paged_decode_work(run.dims, n), run.peak)
                for n in ctx)
    return 100.0 * least / t
