"""Engine and pool (``serving/engine.py``, ``core/prefill.py``): blocking
device->host reads on the engine thread (``d2h_reads``, counted by
``repro.tracing.to_host``) per decode step in the window, prefill reads
included. Nothing where the program has no such counter. Moves
``output_tok_s``."""


def read(run):
    if "d2h_reads" not in run.snap["w0"]["counters"]:
        return None
    steps = run.delta("decode_steps")
    return run.delta("counters", "d2h_reads") / steps if steps > 0 else None
