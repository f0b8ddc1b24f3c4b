"""Whole model step: model FLOPs of the prompt tokens computed and the
tokens decoded in the window, over the window times the chip's bf16
peak, in %. Moves ``output_tok_s``."""
from chipbench.flops import decode_flops, prefill_flops


def read(run):
    if run.peak is None:
        return None
    fl = sum(prefill_flops(run.dims, a, s)
             for a, s in run.prefilled_in(run.w0, run.w1))
    fl += sum(decode_flops(run.dims, n) for n in run.decoded_in(run.w0, run.w1))
    if fl == 0:
        return None
    return 100.0 * fl / (run.seconds * run.peak.flops_bf16)
