"""The 67B head-parallel share (``deepseek-llm-67b.tp8``) on the CPU.

* The served path, at the share's ratios (8 query heads over 1 KV head,
  a feed-forward width that is not a multiple of 128), is correct
  against the plain reference: the one harness run on the CPU with
  grouped-query attention.
* The share ties to the whole model: on a tiny whole layer of 64 query
  and 8 KV heads split 8 ways, the heads' shares of the attention add
  up to the layer's attention, the feed-forward is counted once, and
  the 8 vocabulary shares' logits side by side are the whole head's.
"""
import io

import numpy as np
import pytest

from chipbench import reference as R
from chipbench import run as RUN
from chipbench.flops import Dims
from chipbench.tests.tiny import tiny_config, tiny_mix
from chipbench.weights import make_weights

CELL = "ds67b_tp8.rag_cold"
SEED = 2**31 + 67
SHARES = 8


def test_share_ratios_serve_correct():
    c = tiny_config("deepseek-llm-67b.tp8")
    c.update(num_hidden_layers=2, hidden_size=256, num_attention_heads=8,
             num_key_value_heads=1, head_dim=32, intermediate_size=688,
             vocab_size=400)
    res = RUN.run_cell(CELL, SEED, 3.0, False, require_chip=False,
                       compile_cache=False, config=c,
                       mix=tiny_mix("rag_cold"), out=io.StringIO())
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["check"]["stream_mismatch"]["value"] == 0
    assert res["check"]["short_streams"]["value"] == 0


# ---------------------------------------------------------------------------
# The share against the whole model
# ---------------------------------------------------------------------------
WHOLE = Dims(layers=1, d_model=128, heads=64, kv_heads=8, head_dim=16,
             d_ff=96, vocab=256)
THETA, EPS = 10000.0, 1e-6
T = R.Q_BLOCK                      # the reference's layer takes whole blocks
# f32 on the CPU: the shares sum the attention's output projection in
# another order than the whole layer's one contraction over 64 x 16, so
# values of order 1 may differ by a few ulps, far below 1e-5
SUM_TOL = 1e-5


@pytest.fixture(scope="module")
def whole():
    import jax
    w = make_weights(WHOLE, SEED, "float32")
    rng = np.random.default_rng(3)
    hid = rng.standard_normal((T, WHOLE.d_model)).astype(np.float32)
    # nonzero norm gains, so that a share that dropped them would show
    gw = dict(w["groups"][0])
    gw["ln1"] = jax.numpy.asarray(
        0.1 * rng.standard_normal(gw["ln1"].shape), np.float32)
    gw["ln2"] = jax.numpy.asarray(
        0.1 * rng.standard_normal(gw["ln2"].shape), np.float32)
    return w, gw, hid


def _layer(m: Dims, gw, hid):
    import jax
    import jax.numpy as jnp
    _e, layer, _h = R._fns(m.layers, m.d_model, m.heads, m.kv_heads,
                           m.head_dim, m.d_ff, THETA, EPS, False)
    pos = jnp.arange(T, dtype=jnp.int32)
    valid = jnp.ones(T, bool)
    with jax.default_matmul_precision("highest"):
        return np.asarray(layer(jnp.asarray(hid), pos, valid, gw,
                                jnp.int32(0)))


def _head_share(gw, s):
    """Share ``s`` of 8: its query heads, its KV head and the rows of
    the output projection its heads feed; every other weight whole."""
    hq = WHOLE.heads // SHARES
    hk = WHOLE.kv_heads // SHARES
    out = dict(gw)
    out["wq"] = gw["wq"][:, :, s * hq:(s + 1) * hq]
    out["wk"] = gw["wk"][:, :, s * hk:(s + 1) * hk]
    out["wv"] = gw["wv"][:, :, s * hk:(s + 1) * hk]
    out["wo"] = gw["wo"][:, s * hq:(s + 1) * hq]
    return out


def _no_ffn(gw):
    return dict(gw, wi=gw["wi"] * 0, wo_ff=gw["wo_ff"] * 0)


SHARE = Dims(layers=1, d_model=WHOLE.d_model, heads=WHOLE.heads // SHARES,
             kv_heads=WHOLE.kv_heads // SHARES, head_dim=WHOLE.head_dim,
             d_ff=WHOLE.d_ff, vocab=WHOLE.vocab // SHARES)


def _attention_shares(gw, hid):
    """Each share's attention output through its own rows of ``wo``
    (the layer with the feed-forward zeroed, less its input)."""
    return [_layer(SHARE, _no_ffn(_head_share(gw, s)), hid) - hid
            for s in range(SHARES)]


def test_head_shares_add_up_to_the_layers_attention(whole):
    _w, gw, hid = whole
    attn = _layer(WHOLE, _no_ffn(gw), hid) - hid
    parts = _attention_shares(gw, hid)
    assert all(np.abs(p).max() > 1e-3 for p in parts)
    np.testing.assert_allclose(sum(parts), attn, rtol=SUM_TOL, atol=SUM_TOL)


def test_ffn_is_counted_once(whole):
    """The whole layer is the residual with every share's attention
    added, then one feed-forward over it: not one per share."""
    _w, gw, hid = whole
    full = _layer(WHOLE, gw, hid)
    mid = hid + sum(_attention_shares(gw, hid))
    ffn_only = dict(gw, wo=gw["wo"] * 0)
    once = _layer(WHOLE, ffn_only, mid)
    np.testing.assert_allclose(once, full, rtol=SUM_TOL, atol=SUM_TOL)
    eight = mid + SHARES * (once - mid)
    assert np.abs(eight - full).max() > 100 * SUM_TOL


def test_vocabulary_shares_concatenate_to_the_whole_head(whole):
    import jax
    import jax.numpy as jnp
    w, _gw, hid = whole
    _e, _l, head = R._fns(1, WHOLE.d_model, WHOLE.heads, WHOLE.kv_heads,
                          WHOLE.head_dim, WHOLE.d_ff, THETA, EPS, False)
    rows = jnp.arange(64, dtype=jnp.int32)
    fn = jnp.asarray(np.random.default_rng(5).standard_normal(
        WHOLE.d_model).astype(np.float32) * 0.1)
    V = WHOLE.vocab // SHARES
    with jax.default_matmul_precision("highest"):
        full = np.asarray(head(jnp.asarray(hid), rows, fn, w["unembed"]))
        parts = [np.asarray(head(jnp.asarray(hid), rows, fn,
                                 w["unembed"][:, s * V:(s + 1) * V]))
                 for s in range(SHARES)]
    assert full.shape == (64, WHOLE.vocab)
    # each logit is the same f32 dot product over d_model, read from a
    # slice of the columns: only the matmul's blocking may differ
    np.testing.assert_allclose(np.concatenate(parts, -1), full,
                               rtol=1e-6, atol=1e-6)
