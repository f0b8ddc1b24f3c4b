"""Record the small device trace the trace-reduction tests read.

    python3 -m chipbench.testdata.record_trace <out.xplane.pb>

On one TPU: a few calls of the program's chunk-attention and paged
decode kernels and of a jitted matmul, each call inside a benchmark
span, with a 50 ms sleep (a device idle gap) between the two rounds.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    out = (argv or sys.argv[1:])[0]
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.chunk_attention.ops import chunk_attention
    from repro.kernels.decode_attention.ops import paged_decode_attention
    A, S, H, Hkv, D, bs, NB = 256, 512, 8, 8, 128, 64, 16
    q = jnp.ones((A, H, D), jnp.float32)
    k = jnp.ones((S, Hkv, D), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)
    qp = jnp.arange(S - A, S, dtype=jnp.int32)
    kc = jnp.zeros(S, jnp.int32)
    kb = jnp.ones((NB, bs, Hkv, D), jnp.float32)
    kpos = jnp.arange(NB * bs, dtype=jnp.int32).reshape(NB, bs)
    rows = jnp.asarray(np.arange(8, dtype=np.int32)[None].repeat(4, 0))
    qd = jnp.ones((4, H, D), jnp.float32)
    qdp = jnp.full((4,), 500, jnp.int32)
    mm = jax.jit(lambda x: x @ x)
    x = jnp.ones((1024, 1024), jnp.float32)

    def round_():
        with jax.profiler.TraceAnnotation("bench.chunk_attention"):
            jax.block_until_ready(chunk_attention(q, k, k, qp, pos, kc,
                                                  num_chunks=16))
        with jax.profiler.TraceAnnotation("bench.paged_decode"):
            jax.block_until_ready(paged_decode_attention(
                qd, kb, kb, kpos, rows, qdp))
        with jax.profiler.TraceAnnotation("bench.matmul"):
            jax.block_until_ready(mm(x))

    round_()                                   # compile outside the trace
    d = tempfile.mkdtemp(prefix="chipbench-record-")
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.trace_window"):
        round_()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.05)
        round_()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(d, ignore_errors=True)
    print(f"recorded {out}: {os.path.getsize(out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
