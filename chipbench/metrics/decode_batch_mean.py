"""Scheduler (``serving/scheduler.py``): mean live requests per decode
step over the window, decoded tokens received over the engine's
``decode_steps``. Moves ``output_tok_s``."""
from chipbench.stats import first_tokens_in, tokens_in


def read(run):
    steps = run.delta("decode_steps")
    decoded = tokens_in(run.records, run.w0, run.w1) - \
        first_tokens_in(run.records, run.w0, run.w1)
    return decoded / steps if steps > 0 else None
