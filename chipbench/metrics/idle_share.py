"""Device: share of the traced interval in which no operation ran on
the device, in %. In an open loop it includes the gaps between
arrivals. Moves ``output_tok_s``."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0 or not run.trace.devices:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)
