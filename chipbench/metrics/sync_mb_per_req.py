"""Engine and pool (``serving/engine.py``, ``serving/kvpool.py``): KV
bytes uploaded into the device twin of the pool (``paged_sync_bytes``)
per request prefilled in the window, in MB. Moves ``output_tok_s``."""


def read(run):
    n = run.delta("prefills")
    return run.delta("counters", "paged_sync_bytes") / 1e6 / n \
        if n > 0 else None
