"""Program spans and device->host read counters (``repro.tracing``).

* An engine served under the JAX profiler records the ``cc.*`` span
  tree of ``repro.tracing``'s docstring, each span inside its parent,
  and the server's engine thread records ``cc.dispatch``/``cc.idle``.
* Every device->host read of a decode step is counted: a step fetches
  its tokens and new KV together.
* With the profiler off a span records nothing.
"""
import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_tiny
from repro.models import model as M
from repro.serving.api import EngineSpec, build_engine
from repro.serving.metrics import ServingCounters
from repro.serving.request import Request, State
from repro.serving.scheduler import SchedulerConfig
from repro.tracing import span, to_host

PARENT = {
    "cc.admit": "cc.step", "cc.prefill": "cc.step", "cc.decode": "cc.step",
    "cc.prefill.plan": "cc.prefill", "cc.prefill.inject": "cc.prefill",
    "cc.prefill.compute": "cc.prefill", "cc.prefill.unpack": "cc.prefill",
    "cc.prefill.capture": "cc.prefill",
    "cc.prefill.write_back": "cc.prefill", "cc.prefill.twin": "cc.prefill",
    "cc.decode.prepare": "cc.decode", "cc.decode.sync": "cc.decode",
    "cc.decode.run": "cc.decode", "cc.decode.mirror": "cc.decode",
}


@pytest.fixture(scope="module")
def world():
    cfg = get_tiny("llama3-8b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _requests(cfg, n, max_new, seed=3):
    rng = np.random.default_rng(seed)
    V = cfg.vocab_size
    return [Request(
        rid=i, system_tokens=rng.integers(0, V, 8).astype(np.int32),
        chunk_tokens=[rng.integers(0, V, 24).astype(np.int32)
                      for _ in range(2)],
        question_tokens=rng.integers(0, V, 10).astype(np.int32),
        max_new_tokens=max_new, arrival_time=0.0) for i in range(n)]


def _engine(world, store=False):
    cfg, params = world
    spec = EngineSpec(
        strategy="cachecraft" if store else "all", use_focus=False,
        pool_blocks=512, decode_bucket_b=4, seq_bucket=512,
        sched=SchedulerConfig(max_batch_tokens=100_000, max_decode_batch=4,
                              max_prefill_batch=2))
    return build_engine(spec, cfg=cfg, params=params,
                        **({} if store else {"store": None}))


def _spans(trace_dir):
    """(start, end, name, args) of every ``cc.*`` host span."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("cc."):
                    s = int(ev.start_ns)
                    out.append((s, s + int(ev.duration_ns),
                                ev.name.split("#")[0], dict(ev.stats)))
    return out


def _parent(spans, i):
    """The shortest other span around span ``i`` (spans of the engine
    thread nest), or None."""
    s, e = spans[i][:2]
    around = [x for j, x in enumerate(spans)
              if j != i and x[0] <= s and e <= x[1]]
    return min(around, key=lambda x: x[1] - x[0], default=None)


def test_engine_records_the_span_tree(world, tmp_path):
    eng = _engine(world, store=True)
    reqs = _requests(world[0], n=3, max_new=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    assert all(r.state == State.DONE for r in reqs)
    spans = _spans(tmp_path)
    assert {x[2] for x in spans} == set(PARENT) | {"cc.step"}
    for i, (_s, _e, name, _args) in enumerate(spans):
        p = _parent(spans, i)
        if name == "cc.step":
            assert p is None
        else:
            assert p is not None and p[2] == PARENT[name], (name, p)
    pre = [x for x in spans if x[2] == "cc.prefill"]
    assert sum(x[3]["n"] for x in pre) == len(reqs)
    # the profiler reads an arg that looks like a number as one
    assert sorted(int(r) for x in pre for r in str(x[3]["rids"]).split()) \
        == [r.rid for r in reqs]
    dec = [x for x in spans if x[2] == "cc.decode"]
    assert len(dec) == eng.stats.decode_steps
    assert all(1 <= x[3]["b"] <= 4 for x in dec)


def test_server_engine_thread_records_dispatch_and_idle(world, tmp_path):
    from repro.serving.server import CacheCraftServer, ServeClient
    server = CacheCraftServer(_engine(world)).start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        client = ServeClient(server.host, server.port)
        rids = [client.submit(r) for r in _requests(world[0], 2, 3)]
        got = {}
        readers = [threading.Thread(
            target=lambda rid=rid: got.update({rid: client.stream(rid)}),
            daemon=True) for rid in rids]
        for t in readers:
            t.start()
        for t in readers:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in readers)
        time.sleep(0.2)                      # a few idle inbox waits
    finally:
        jax.profiler.stop_trace()
        server.shutdown()
    assert all(state == State.DONE.value for _toks, state in got.values())
    spans = _spans(tmp_path)
    names = {x[2] for x in spans}
    assert {"cc.step", "cc.dispatch", "cc.idle"} <= names
    for i, x in enumerate(spans):
        if x[2] in ("cc.dispatch", "cc.idle"):
            assert _parent(spans, i) is None


def test_decode_step_reads_two_arrays(world):
    """A decode step makes one fetch of two arrays (tokens and new KV),
    whatever the live rows and layers."""
    cfg, _ = world
    eng = _engine(world)
    reqs = _requests(cfg, n=3, max_new=8)
    for r, n in zip(reqs, (4, 6, 8)):
        r.max_new_tokens = n
    for r in reqs:
        eng.submit(r)
    while eng.scheduler.queue:                 # admit all three
        eng.step()
    seen = set()
    while eng.decoding:                        # decode-only steps
        live = len(eng.decoding)
        seen.add(live)
        assert not eng.scheduler.queue
        before = eng.counters.d2h_reads
        eng.step()
        assert eng.counters.d2h_reads - before == 2
    assert seen == {1, 2, 3}


def test_prefill_reads_are_counted(world):
    eng = _engine(world, store=True)
    eng.submit(_requests(world[0], n=1, max_new=2)[0])
    eng.step()
    c = eng.counters
    assert eng.stats.prefills == 1 and eng.stats.decode_steps == 1
    # unpack alone brings the whole packed K and V back (66 tokens)
    L, hkv, dh = world[0].num_layers, world[0].num_kv_heads, \
        world[0].head_dim_
    assert c.d2h_bytes > 2 * L * 66 * hkv * dh * 4
    assert c.d2h_reads > 1 + 2 * len(world[0].pattern)
    assert eng.stats_dict()["counters"]["d2h_reads"] == c.d2h_reads


def test_prefill_rows_reach_stats(world):
    """The prefill's row counters reach the engine's counters and
    ``/stats``: one fresh 66-token prompt, every row computed, padded
    to the executor's bucket in every layer."""
    eng = _engine(world)
    eng.submit(_requests(world[0], n=1, max_new=2)[0])
    eng.step()
    L, b = world[0].num_layers, eng.executor.bucket
    d = eng.stats_dict()["counters"]
    assert d["prefill_rows_active"] == 66 * L
    assert d["prefill_rows_run"] == -(-66 // b) * b * L


def test_span_with_profiler_off_records_nothing(tmp_path):
    with span("cc.before", n=1, rids="1 2"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("cc.inside", b=2):
            pass
    finally:
        jax.profiler.stop_trace()
    with span("cc.after"):
        pass
    spans = _spans(tmp_path)
    assert [(x[2], x[3]) for x in spans] == [("cc.inside", {"b": 2})]


def test_to_host_counts_device_reads_only():
    c = ServingCounters()
    x = jnp.ones((4, 8), jnp.float32)
    out = to_host(x, c)
    assert isinstance(out, np.ndarray) and out.shape == (4, 8)
    assert (c.d2h_reads, c.d2h_bytes) == (1, 128)
    to_host(np.ones(3), c)                   # already on the host
    to_host(x)                               # no counters given
    assert (c.d2h_reads, c.d2h_bytes) == (1, 128)
    a, b = to_host((x, jnp.arange(3, dtype=jnp.int32)), c)  # one fetch
    assert a.shape == (4, 8) and b.tolist() == [0, 1, 2]
    assert (c.d2h_reads, c.d2h_bytes) == (3, 128 + 128 + 12)
