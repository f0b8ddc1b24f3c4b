"""Memory rehearsal without a chip: compile a configuration's serving
steps for a described TPU v5e and print what each needs.

    JAX_PLATFORMS=cpu python3 -m chipbench.aot_memory deepseek-llm-7b

For the configuration's depth and pool it compiles, at the largest
shapes its cells reach: the prefill window over all layers on the
chunk-attention kernel (active rows = prompt = the largest prompt
bucket), the paged decode step (the decode batch, the most block rows)
and the reference's layer. Nothing runs; it reads each program's
``memory_analysis()``. The weights are counted once, as the arguments
every step shares.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
GiB = float(1 << 30)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    ap.add_argument("--mix", default="rag_cold")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--layers", type=int, default=0,
                    help="try another depth than the configuration's")
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="try another pool size than the configuration's")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench.flops import Dims
    from chipbench.run import _sweep_lengths, program_config
    from chipbench.weights import make_weights
    from chipbench import reference as R
    from repro.core.prefill import _window_fn, paged_decode_fn
    import repro.kernels.chunk_attention.ops as ca_ops
    import repro.kernels.decode_attention.ops as da_ops

    # the kernels pick interpret mode on a CPU backend; here they are
    # compiled for the described chip, so compile them for real
    def compiled(interpret):
        return False if interpret is None else interpret
    ca_ops.interpret_default = da_ops.interpret_default = compiled

    c = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    mix = json.loads((HERE / "traffic" / f"{args.mix}.json").read_text())
    if args.layers:
        c["num_hidden_layers"] = args.layers
    if args.pool_blocks:
        c["engine"]["pool_blocks"] = args.pool_blocks
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    one = SingleDeviceSharding(topo.devices[0])
    cfg = program_config(c)
    m = Dims.from_config(c)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    w = jax.eval_shape(lambda: make_weights(m, 0, c["precision"]["weights"]))
    w = jax.tree.map(lambda x: sds(x.shape, x.dtype), w)
    wbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(w))
    e = c["engine"]
    b = int(c["compile"]["prefill_bucket"])
    lo, hi = _sweep_lengths(mix)
    S = -(-hi // b) * b
    L, hkv, dh = m.layers, m.kv_heads, m.head_dim
    i32 = jnp.int32
    rows = []

    def report(name, compiled):
        ma = compiled.memory_analysis()
        args_ = ma.argument_size_in_bytes
        rows.append((name, args_, ma.output_size_in_bytes,
                     ma.alias_size_in_bytes, ma.temp_size_in_bytes))
        n = compiled.as_text().count("tpu_custom_call")
        print(f"{name}: arguments {args_ / GiB:.2f} GiB, output "
              f"{ma.output_size_in_bytes / GiB:.2f} GiB (aliased "
              f"{ma.alias_size_in_bytes / GiB:.2f}), temporaries "
              f"{ma.temp_size_in_bytes / GiB:.2f} GiB, "
              f"{n} tpu_custom_call", flush=True)

    # prefill window, every layer, chunk-attention kernel
    cache = {"groups": [{"k": sds((L, 1, S, hkv, dh)),
                         "v": sds((L, 1, S, hkv, dh)),
                         "pos": sds((L, 1, S), i32)}], "tail": []}
    win = _window_fn(cfg).lower(
        w, sds((1, S, m.d_model)), sds((1, S), i32), sds((1, S), i32),
        cache, sds((1, S), i32), sds((1, S), i32), sds((1, S), i32),
        None, None, g0=0, g1=L, tail=False, collect=True,
        attn_impl="kernel").compile()
    report(f"prefill window A=S={S}", win)

    # paged decode step over the pool twin
    bs, nb = int(e["block_size"]), int(e["pool_blocks"])
    B, Sd = int(e["decode_bucket_b"]), int(e["seq_bucket"])
    most = -(-(hi + int(mix["output"]["max"]) + 1) // bs) + \
        mix["retrieval"]["k"] + 3
    nbm = -(-most // 8) * 8
    twin = {"groups": [{"kp": sds((L, nb * bs, hkv, dh)),
                        "vp": sds((L, nb * bs, hkv, dh)),
                        "ppos": sds((L, nb * bs), i32)}], "tail": []}
    dec = paged_decode_fn(cfg, e["attn_impl"], bs).lower(
        w, sds((B,), i32), sds((B,), i32), twin, sds((B,), i32),
        sds((B, Sd), i32), sds((B, nbm), i32)).compile()
    report(f"paged decode B={B} block rows={nbm} pool={nb}x{bs}", dec)

    # the reference's layer at the longest sequence it reads
    T = -(-(hi + int(mix["output"]["max"])) // R.Q_BLOCK) * R.Q_BLOCK
    _e, layer, _h = R._fns(m.layers, m.d_model, m.heads, m.kv_heads,
                           m.head_dim, m.d_ff, float(c["rope_theta"]),
                           float(c["rms_norm_eps"]), False)
    with jax.default_matmul_precision("highest"):
        ref = layer.lower(sds((T, m.d_model)), sds((T,), i32),
                          sds((T,), jnp.bool_), w["groups"][0],
                          sds((), i32)).compile()
    report(f"reference layer T={T}", ref)

    twin_bytes = 2 * L * nb * bs * hkv * dh * 4
    print(f"weights {wbytes / GiB:.2f} GiB; pool twin {twin_bytes / GiB:.2f}"
          f" GiB; peak step (decode: arguments + temporaries) "
          f"{(rows[1][1] + rows[1][4]) / GiB:.2f} GiB; prefill window "
          f"beside weights and twin "
          f"{(wbytes + twin_bytes + rows[0][1] - wbytes + rows[0][4]) / GiB:.2f}"
          f" GiB of the chip's 15.75", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
