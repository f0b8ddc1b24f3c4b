"""The benchmark's FLOP counts against the HLO count of one small
compiled call (``launch/roofline.py``'s analysis): the chunk-attention
kernel's oracle, the decode attention, and a whole forward pass."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import flops as F
from repro.launch.roofline import analyze_hlo

M = F.Dims(layers=2, d_model=128, heads=4, kv_heads=2, head_dim=32,
           d_ff=256, vocab=512, stat_chunks=16)


def hlo_flops(fn, *args):
    return analyze_hlo(jax.jit(fn).lower(*args).compile().as_text()).flops


def test_chunk_attention_flops_match_hlo():
    from repro.kernels.chunk_attention.ref import chunk_attention_ref
    A, S = 64, 128
    one = F.Dims(**{**M.__dict__, "layers": 1})
    args = (jnp.zeros((A, M.heads, M.head_dim)),
            jnp.zeros((S, M.kv_heads, M.head_dim)),
            jnp.zeros((S, M.kv_heads, M.head_dim)),
            jnp.zeros(A, jnp.int32), jnp.zeros(S, jnp.int32),
            jnp.zeros(S, jnp.int32))
    got = hlo_flops(lambda *a: chunk_attention_ref(
        *a, num_chunks=M.stat_chunks), *args)
    assert F.chunk_attn_work(one, A, S)[0] == got


def test_decode_attention_flops_match_hlo():
    from repro.models.layers import decode_attend
    S = 256
    one = F.Dims(**{**M.__dict__, "layers": 1})
    args = (jnp.zeros((1, M.heads, M.head_dim)),
            jnp.zeros((1, S, M.kv_heads, M.head_dim)),
            jnp.zeros((1, S, M.kv_heads, M.head_dim)),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1, S), jnp.int32))
    got = hlo_flops(decode_attend, *args)
    assert F.paged_decode_work(one, S)[0] == got


def test_model_flops_per_token_match_hlo():
    """A full forward over T tokens computes, per the HLO: T tokens
    through every layer, attention over the whole T-by-T rectangle and
    the head for every row."""
    from repro.models import model as M_
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="t", family="dense", num_layers=M.layers,
                      d_model=M.d_model, num_heads=M.heads,
                      num_kv_heads=M.kv_heads, head_dim=M.head_dim,
                      d_ff=M.d_ff, vocab_size=M.vocab, remat=False)
    T = 64
    params = M_.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((1, T), jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    got = hlo_flops(lambda p, t, q: M_.forward(
        cfg, p, tokens=t, positions=q, attn_impl="dense").logits,
        params, toks, pos)
    want = (T * M.layers * F.layer_matmul_flops(M)
            + F.attention_flops(M, T, T) + F.head_flops(M, T))
    assert want == pytest.approx(got, rel=0.01)


def test_roofline_seconds_takes_the_longer_bound():
    from chipbench.peaks import peak_for
    p = peak_for("TPU v5 lite")
    assert F.roofline_seconds(197e12, 0, p) == pytest.approx(1.0)
    assert F.roofline_seconds(0, 819e9, p) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peak_for("TPU v9 imaginary")
