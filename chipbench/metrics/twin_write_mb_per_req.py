"""Engine and pool (``serving/engine.py``, ``core/prefill.py``): KV
bytes a prefill's own device KV wrote into the device twin of the pool
(``twin_write_bytes``, no host upload) per request prefilled in the
window, in MB. Nothing where the program has no such counter. Moves
``output_tok_s``."""


def read(run):
    if "twin_write_bytes" not in run.snap["w0"]["counters"]:
        return None
    n = run.delta("prefills")
    return run.delta("counters", "twin_write_bytes") / 1e6 / n \
        if n > 0 else None
