"""The generator gives every seed the same work: sizes, output budgets
and arrivals come from the mix's ``shape_seed``, token ids from the
run's seed."""
import json
from pathlib import Path

import numpy as np

from chipbench.tests.tiny import tiny_reuse_mix
from chipbench.traffic import Generator

MIX = json.loads((Path(__file__).resolve().parents[1] / "traffic" /
                  "rag_cold.json").read_text())
BIG = 2**31 + 11


def shapes(reqs):
    return [(r.due, r.max_new, len(r.system), [len(c) for c in r.chunks],
             len(r.question)) for r in reqs]


def test_seeds_share_sizes_and_differ_in_tokens():
    a, b = Generator(MIX, 102400, BIG), Generator(MIX, 102400, 7)
    ra, rb = a.window(51), b.window(51)
    assert shapes(ra) == shapes(rb)
    assert shapes(a.warmup()) == shapes(b.warmup())
    assert not np.array_equal(ra[0].prompt, rb[0].prompt)
    again = Generator(MIX, 102400, BIG).window(51)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(ra, again))


def test_fresh_mix_shares_no_chunk_and_keeps_to_its_lengths():
    reqs = Generator(MIX, 102400, BIG).window(51)[:64]
    kb = MIX["kb"]
    seen = set()
    for r in reqs:
        assert len(r.chunks) == MIX["retrieval"]["k"]
        for c in r.chunks:
            assert kb["len_min"] <= len(c) <= kb["len_max"]
            assert len(c) % kb["len_quantum"] == 0
            seen.add(c.tobytes())
    assert len(seen) == 64 * MIX["retrieval"]["k"]


def test_shared_mix_reuses_chunks_and_spaces_arrivals():
    mix = tiny_reuse_mix()
    reqs = Generator(mix, 512, BIG).window(10.0)
    n = round(mix["arrival"]["rate_per_s"] * (mix["lead_in_s"] + 10.0))
    assert len(reqs) == n
    dues = [r.due for r in reqs]
    assert dues == sorted(dues) and -mix["lead_in_s"] <= dues[0]
    assert dues[-1] < 10.0
    chunks = [c.tobytes() for r in reqs for c in r.chunks]
    assert len(set(chunks)) < len(chunks)
