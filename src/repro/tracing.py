"""Host spans and device->host read counters for the engine-loop thread.

Spans are written into the JAX profiler's own trace
(``jax.profiler.TraceAnnotation``), on the clock of its device events,
so a gap in the device timeline is named by the innermost span around
it. Starting and stopping the profiler is the only switch: with it off
a span costs about a microsecond. Only the engine-loop thread, which
owns the device, records spans. Every name starts with ``cc.``::

    cc.step                 Engine.step
      cc.admit              cancels, expiry, look-ahead, admission,
                            reclaim and preemption
      cc.prefill            Engine._run_prefills (n requests, rids)
        cc.prefill.plan     build_plan and the deviation probe
        cc.prefill.inject   hit loads, inject_chunk_kv, layout arrays
        cc.prefill.compute  embed, layer windows and stats reads, head
        cc.prefill.unpack   unpack_cache (the packed KV to the host)
        cc.prefill.capture  store inserts
        cc.prefill.write_back  pool write-back, decode-batch joins
        cc.prefill.twin     prefill blocks copied into the pool twin,
                            device to device
      cc.decode             one decode step (b live rows)
        cc.decode.prepare   slot opening, rebuild, index and block rows
        cc.decode.sync      device twin upload (pack or dirty blocks)
        cc.decode.run       the jitted step and its one read (tokens
                            and new KV)
        cc.decode.mirror    pool append, token emit, completion
    cc.dispatch             the server's token fan-out
    cc.idle                 the server's blocking inbox wait
"""
from __future__ import annotations

import jax
import numpy as np


def span(name: str, **args):
    """A profiler span; ``args`` (ints or strings) ride along as the
    event's stats."""
    return jax.profiler.TraceAnnotation(name, **args)


def to_host(x, counters=None):
    """``np.asarray(x)``, or a tuple of them fetched in one blocking
    ``jax.device_get``. Each device array is a device->host read: counted
    in ``counters.d2h_reads`` / ``d2h_bytes`` when given."""
    xs = x if isinstance(x, tuple) else (x,)
    out = tuple(np.asarray(a) for a in jax.device_get(xs))
    if counters is not None:
        for a, o in zip(xs, out):
            if isinstance(a, jax.Array):
                counters.d2h_reads += 1
                counters.d2h_bytes += o.nbytes
    return out if isinstance(x, tuple) else out[0]
