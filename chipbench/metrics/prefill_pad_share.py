"""Whole model step (``core/prefill.py``): the share of the rows the
prefill's layer windows computed in the window that carried no
request's token, bucket padding (``prefill_rows_run`` less
``prefill_rows_active``, both times each window's layers), in %.
Nothing where the program has no such counter or no prefill ran. Moves
``output_tok_s``."""


def read(run):
    if "prefill_rows_run" not in run.snap["w0"]["counters"]:
        return None
    ran = run.delta("counters", "prefill_rows_run")
    if ran <= 0:
        return None
    active = run.delta("counters", "prefill_rows_active")
    return 100.0 * (ran - active) / ran
