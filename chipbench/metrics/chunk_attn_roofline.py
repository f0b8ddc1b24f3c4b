"""Kernels: the chunk-attention kernel's share of its roofline in the
traced part of the window, in %. The numerator is the least time the
chip could take for the kernel's work, from the sizes of the prefills
whose first token arrived in the traced interval (``chipbench/flops.py``);
the denominator is the device time of the kernel's events. Moves
``output_tok_s``."""
from chipbench.flops import chunk_attn_work, roofline_seconds

# The serving path runs two Pallas kernels, and the chunk-attention one
# carries no name in the trace (its custom call is named after the vmap
# around it): it is the TPU custom call that is not the paged decode.
KERNEL = r'^(?!%?paged_decode_attention)[^=]*=.*"tpu_custom_call"'



def read(run):
    if run.trace is None or run.peak is None:
        return None
    t = run.trace.op_seconds(KERNEL)
    work = run.prefilled_in(run.trace_t0, run.trace_t1)
    if t <= 0 or not work:
        return None
    least = 0.0
    for computed, total in work:
        fl, by = chunk_attn_work(run.dims, computed, total)
        least += roofline_seconds(fl, by, run.peak)
    return 100.0 * least / t
