"""The prefill padding reader: the share of the rows the prefill's layer
windows computed that carried no request's token, from the program's
``prefill_rows_run`` and ``prefill_rows_active`` counters, and nothing
where the program lacks them."""
import numpy as np
import pytest

from chipbench import run as R
from chipbench.flops import Dims
from chipbench.tests.tiny import tiny_config
from chipbench.weights import make_weights

SEED = 2**31 + 5


def _run(c0, c1):
    snap = {"w0": {"decode_steps": 10, "prefills": 4, "counters": c0},
            "w1": {"decode_steps": 60, "prefills": 7, "counters": c1}}
    return R.RunData(dims=None, peak=None, seconds=51.0, w0=0.0, w1=51.0,
                     records=[], requests={}, prompts={}, snap=snap)


def test_pad_share_counter_absent_reads_as_nothing():
    """A program without the counters (the parent of the change that
    added them) gives no reading and does not fail; nor does a window
    in which no prefill ran."""
    read = R.metric_reader("prefill_pad_share")
    assert read(_run({"d2h_reads": 0}, {"d2h_reads": 9})) is None
    idle = {"prefill_rows_run": 4096, "prefill_rows_active": 3000}
    assert read(_run(idle, dict(idle))) is None


def test_pad_share_from_a_tiny_run(tmp_path):
    """Two fresh prompts through the engine at the tiny configuration's
    bucket of 128 rows: every token is computed, so each window runs the
    prompt's rows rounded up to the bucket."""
    from repro.serving.api import build_engine
    from repro.serving.request import Request
    c = tiny_config()
    bucket = c["compile"]["prefill_bucket"]
    cfg = R.program_config(c)
    weights = make_weights(Dims.from_config(c), SEED,
                           c["precision"]["weights"])
    eng = build_engine(R.engine_spec(c, str(tmp_path)), cfg=cfg,
                       params=weights)
    R.set_prefill_bucket(eng, bucket)
    rng = np.random.default_rng(SEED)
    V = c["vocab_size"]

    def request(rid, chunk_lens):
        return Request(
            rid=rid, system_tokens=rng.integers(0, V, 8).astype(np.int32),
            chunk_tokens=[rng.integers(0, V, n).astype(np.int32)
                          for n in chunk_lens],
            question_tokens=rng.integers(0, V, 8).astype(np.int32),
            max_new_tokens=2, arrival_time=0.0)
    try:
        w0 = R._snapshot(eng)
        stats = eng.run([request(0, (48, 48, 48)), request(1, (32, 44))])
        w1 = R._snapshot(eng)
    finally:
        eng.store.tiers.close()
    assert stats.completed == 2 and stats.failed == 0
    prompts = (8 + 3 * 48 + 8, 8 + 32 + 44 + 8)          # 160 and 92 rows
    run = sum(-(-n // bucket) * bucket for n in prompts)  # 256 + 128
    share = R.metric_reader("prefill_pad_share")(_run(
        w0["counters"], w1["counters"]))
    assert share == pytest.approx(100.0 * (run - sum(prompts)) / run)
    L = c["num_hidden_layers"]
    assert w1["counters"]["prefill_rows_active"] - \
        w0["counters"]["prefill_rows_active"] == L * sum(prompts)
