"""The harness end to end on the CPU at a tiny size, with the look for
a chip skipped: a sound run is correct, a run with a served token
altered where the engine produces it is not, and requests that reuse
chunk caches made under other prefixes are compared like any other."""
import io
import json

import pytest

from chipbench import run as R
from chipbench.tests.tiny import tiny_config, tiny_mix, tiny_reuse_mix

SEED = 2**31 + 17


def run(cell, mix, **kw):
    buf = io.StringIO()
    res = R.run_cell(cell, SEED, 3.0, False, require_chip=False,
                     compile_cache=False, config=tiny_config(), mix=mix,
                     out=buf, **kw)
    assert json.loads(buf.getvalue().strip().splitlines()[-1]) == \
        json.loads(json.dumps(res))
    return res


@pytest.mark.parametrize("cell,mix", [("ds7b.rag_cold", "rag_cold")])
def test_sound_run_is_correct(cell, mix):
    res = run(cell, tiny_mix(mix))
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res["check"]) == ["gap_max", "gap_mean", "stream_mismatch",
                                  "short_streams"]
    assert list(res)[-1] == "check"
    assert "setup_s" in res["metrics"] and "output_tok_s" in res["metrics"]


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    from repro.serving.engine import Engine
    emit = Engine._emit_token

    def altered(self, req, token):
        if len(req.output_tokens) == 2:       # every request's 2nd token
            token = (token + 1) % self.cfg.vocab_size
            req.output_tokens[-1] = token
        return emit(self, req, token)
    monkeypatch.setattr(Engine, "_emit_token", altered)
    res = run("ds7b.rag_cold", tiny_mix("rag_cold"))
    assert not res["correct"]
    assert res["check"]["gap_max"]["value"] > \
        res["check"]["gap_max"]["limit"]


def test_reused_requests_are_compared():
    """Reuse departs from full recompute by design (Cache-Craft holds it
    to quality, not equality), so at limits set for requests computed
    whole a run over shared chunks is not correct: the check compares
    reused requests and passes none over."""
    res = run("ds7b.rag_cold", tiny_reuse_mix())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["check"]["stream_mismatch"]["value"] == 0
    assert res["check"]["gap_mean"]["value"] > \
        res["check"]["gap_mean"]["limit"]
    assert not res["correct"]
