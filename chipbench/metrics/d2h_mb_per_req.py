"""Engine and pool (``serving/engine.py``, ``core/prefill.py``): bytes
of the blocking device->host reads on the engine thread (``d2h_bytes``,
counted by ``repro.tracing.to_host``) per request prefilled in the
window, in MB. Nothing where the program has no such counter. Moves
``output_tok_s``."""


def read(run):
    if "d2h_bytes" not in run.snap["w0"]["counters"]:
        return None
    n = run.delta("prefills")
    return run.delta("counters", "d2h_bytes") / 1e6 / n if n > 0 else None
