"""The one traffic generator. A mix is a data file,
``chipbench/traffic/<mix>.json``; this module reads it and draws every
request from ``--seed``.

The structure follows the program's RAG workload (``serving/rag.py``,
``serving/workload.py``): a knowledge base of token chunks, Zipf chunk
popularity, sessions with their own system prompt whose queries share
retrieval sets, and a question that quotes the retrieved chunks. It is a
copy, kept here so that a change to the program cannot move the
yardstick, at realistic lengths: chunk lengths are lognormal, cut to a
multiple of ``len_quantum`` tokens as a fixed-size chunker emits them.

Every seed gets the same work: lengths, output budgets, arrival gaps,
popularity ranks, sessions and retrieval sets are drawn from the mix's
fixed ``shape_seed``; the run's seed draws the token ids (and the
weights). An open loop offers exactly ``round(rate * (lead-in +
window))`` requests, with Poisson gaps scaled to fill that span.

Mix keys:

* ``arrival``: ``{"kind": "poisson", "rate_per_s": r}`` (open loop) or
  ``{"kind": "closed", "clients": c}`` (each client sends its next
  request when the last one has finished).
* ``lead_in_s``: traffic sent before the measured window, at the same
  rate, so that the window starts in steady state.
* ``kb``: ``chunks``, ``len_median``, ``len_sigma``, ``len_min``,
  ``len_max``, ``len_quantum``; ``fresh: true`` gives every request
  chunks and a system prompt of its own that no other request shares.
* ``retrieval``: ``k`` chunks per request, ``zipf_a``, ``sessions``,
  ``queries_per_session``.
* ``system_len``, ``question_len``.
* ``output``: lognormal ``median``, ``sigma``, cut to [``min``, ``max``].
* ``warmup_requests``: requests from a separate seed stream, served
  before the window to fill the chunk store; ``warmup_max_new`` caps
  their outputs, which nothing reads.
* ``check_requests``: how many finished requests the output check
  compares, the longest among them.
* ``shape_seed``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

# seed streams
_KB, _WINDOW, _WARMUP, _CHECK, _SESSION, _SYSTEM = range(1, 7)


@dataclass
class Req:
    """One request to send. ``due`` is seconds from the window's start
    (negative in the lead-in); ``None`` in a closed loop."""
    idx: int
    due: Optional[float]
    session: int
    system: np.ndarray
    chunks: List[np.ndarray]
    question: np.ndarray
    max_new: int

    @property
    def prompt(self) -> np.ndarray:
        return np.concatenate([self.system, *self.chunks, self.question])

    def body(self) -> dict:
        """The ``/v1/submit`` JSON body."""
        return dict(system_tokens=self.system.tolist(),
                    chunk_tokens=[c.tolist() for c in self.chunks],
                    question_tokens=self.question.tolist(),
                    max_new_tokens=int(self.max_new),
                    session=int(self.session))


def _rng(seed: int, *stream) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  int(seed) >> 32, *stream])


def _lengths(rng, n, kb) -> np.ndarray:
    raw = rng.lognormal(math.log(kb["len_median"]), kb["len_sigma"], n)
    q = kb["len_quantum"]
    out = np.round(raw / q).astype(np.int64) * q
    return np.clip(out, kb["len_min"], kb["len_max"])


def _outputs(rng, n, spec) -> np.ndarray:
    raw = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.round(raw), spec["min"], spec["max"]).astype(np.int64)


class Generator:
    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix, self.vocab, self.seed = mix, vocab, seed
        kb, ret = mix["kb"], mix["retrieval"]
        shape = np.random.default_rng([mix["shape_seed"], _KB])
        self.fresh = bool(kb.get("fresh", False))
        rng = _rng(seed, _KB)
        self.chunks: List[np.ndarray] = []
        if not self.fresh:
            lens = _lengths(shape, kb["chunks"], kb)
            self.chunks = [rng.integers(0, vocab, n).astype(np.int32)
                           for n in lens]
            ranks = np.arange(1, kb["chunks"] + 1, dtype=np.float64)
            pop = ranks ** (-ret["zipf_a"])
            self.popularity = pop / pop.sum()
            self.rank_to_chunk = shape.permutation(kb["chunks"])
        self._sets: Dict[tuple, List[int]] = {}
        self._systems: Dict[int, np.ndarray] = {}

    # ---- building blocks --------------------------------------------------
    def _system(self, session: int) -> np.ndarray:
        if session not in self._systems:
            r = _rng(self.seed, _SYSTEM, session)
            self._systems[session] = r.integers(
                0, self.vocab, self.mix["system_len"]).astype(np.int32)
        return self._systems[session]

    def _retrieve(self, session: int, query: int) -> List[int]:
        """The chunk ids of one session's query: k distinct chunks by
        Zipf popularity, fixed per (session, query) so that a session's
        queries share their retrieval sets."""
        key = (session, query)
        if key not in self._sets:
            r = np.random.default_rng([self.mix["shape_seed"], _SESSION,
                                       session, query])
            k = self.mix["retrieval"]["k"]
            ids: List[int] = []
            while len(ids) < k:
                c = int(self.rank_to_chunk[r.choice(
                    len(self.popularity), p=self.popularity)])
                if c not in ids:
                    ids.append(c)
            self._sets[key] = ids
        return self._sets[key]

    def _question(self, rng, chunks: List[np.ndarray]) -> np.ndarray:
        """Question tokens that quote 4-grams of half the retrieved
        chunks, padded with random ids."""
        n = self.mix["question_len"]
        pick = rng.choice(len(chunks), size=max(1, len(chunks) // 2),
                          replace=False)
        parts = []
        for i in pick:
            c = chunks[i]
            s = int(rng.integers(0, max(1, len(c) - 4)))
            parts.append(c[s:s + 4])
        q = np.concatenate(parts)[:n]
        pad = rng.integers(0, self.vocab, n - len(q))
        return np.concatenate([q, pad]).astype(np.int32)

    def _fresh_prompt(self, rng, lens):
        chunks = [rng.integers(0, self.vocab, int(m)).astype(np.int32)
                  for m in lens]
        system = rng.integers(0, self.vocab,
                              self.mix["system_len"]).astype(np.int32)
        return system, chunks

    def _requests(self, stream: int, n: int, dues,
                  max_new: Optional[int] = None) -> List[Req]:
        """``n`` requests: fresh (chunks and a system prompt of their
        own) where the mix's knowledge base is ``fresh``, else a
        session's system prompt and a retrieval set from the knowledge
        base."""
        mix = self.mix
        k = mix["retrieval"]["k"]
        shape = np.random.default_rng([mix["shape_seed"], stream])
        outs = _outputs(shape, n, mix["output"])
        lens = _lengths(shape, n * k, mix["kb"])
        ret = mix["retrieval"]
        sessions = shape.integers(0, max(1, ret["sessions"]), n)
        queries = shape.integers(0, ret["queries_per_session"], n)
        rng = _rng(self.seed, stream)
        reqs: List[Req] = []
        for i in range(n):
            if self.fresh:
                system, chunks = self._fresh_prompt(rng, lens[i * k:(i + 1) * k])
                session = -1
            else:
                session, query = int(sessions[i]), int(queries[i])
                chunks = [self.chunks[c]
                          for c in self._retrieve(session, query)]
                system = self._system(session)
            reqs.append(Req(idx=i, due=None if dues is None else dues[i],
                            session=session, system=system, chunks=chunks,
                            question=self._question(rng, chunks),
                            max_new=int(max_new or outs[i])))
        return reqs

    # ---- what a run sends -------------------------------------------------
    def window(self, seconds: float, round_: int = 0) -> List[Req]:
        """The requests of the lead-in and the measured window. Open loop:
        Poisson arrivals at the mix's rate, ``due`` from the window's
        start. Closed loop: enough requests for the clients to never run
        dry, in the order they are to be sent. ``round_`` > 0 draws
        another window of the same run (a rate sweep)."""
        arr = self.mix["arrival"]
        lead = float(self.mix["lead_in_s"])
        stream = _WINDOW + 100 * round_
        if arr["kind"] == "closed":
            return self._requests(stream, int(arr["max_requests"]), None)
        rate = float(arr["rate_per_s"])
        span = lead + seconds
        n = max(1, int(round(rate * span)))
        shape = np.random.default_rng([self.mix["shape_seed"], stream, 1])
        # Poisson gaps, scaled so that exactly n arrivals fill the span
        gaps = shape.exponential(1.0 / rate, n + 1)
        dues = np.cumsum(gaps)[:n] * (span / gaps.sum()) - lead
        return self._requests(stream, n, dues.tolist())

    def warmup(self) -> List[Req]:
        return self._requests(_WARMUP, int(self.mix["warmup_requests"]), None,
                              self.mix.get("warmup_max_new"))


def check_sample(finished: List[int], lengths: Dict[int, int], n: int,
                 seed: int) -> List[int]:
    """The requests the output check compares, from ``finished``: the
    longest one and ``n - 1`` more drawn from the seed."""
    if not finished:
        return []
    longest = max(finished, key=lambda i: (lengths[i], i))
    rest = [i for i in sorted(finished) if i != longest]
    pick = _rng(seed, _CHECK).permutation(len(rest))[:max(0, n - 1)]
    return [longest] + sorted(rest[i] for i in pick)
