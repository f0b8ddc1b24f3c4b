"""The program's spans in a trace (``chipbench/phases.py``) and the
host-sync counter readers: device-idle time by innermost span, per
prefilled request and per decode step, module time, gap names, and the
counters read as nothing where the program lacks them."""
from pathlib import Path

import pytest

from chipbench import phases as PH
from chipbench import trace as T
from chipbench.run import RunData, metric_reader

MS = 1_000_000
DATA = Path(__file__).resolve().parents[1] / "testdata" / "tpu_small.xplane.pb"


def synthetic():
    """100 ms traced: device busy 0-10, 40-50 and 90-100 ms; one step
    holding a prefill of two requests (10-40 ms, inside the benchmark's
    own span) and a decode (50-90 ms)."""
    ops = [T.Op(0, 10 * MS, "fusion.1", "fusion.1"),
           T.Op(40 * MS, 50 * MS, "fusion.2", "fusion.2"),
           T.Op(90 * MS, 100 * MS, "fusion.3", "fusion.3")]
    tr = T.Trace(devices={"/device:TPU:0": ops}, spans=[], window_s=0.1,
                 t0_ns=0, t1_ns=100 * MS)
    S = PH.Span
    spans = [S(0, 100 * MS, T.WINDOW_SPAN),
             S(5 * MS, 95 * MS, "bench.engine.step"),
             S(5 * MS, 95 * MS, "cc.step"),
             S(5 * MS, 8 * MS, "cc.admit"),
             S(8 * MS, 45 * MS, "cc.prefill", {"n": 2, "rids": "4 5"}),
             S(9 * MS, 44 * MS, "bench.executor.prefill"),
             S(10 * MS, 20 * MS, "cc.prefill.compute"),
             S(20 * MS, 38 * MS, "cc.prefill.unpack"),
             S(45 * MS, 92 * MS, "cc.decode", {"b": 3}),
             S(46 * MS, 60 * MS, "cc.decode.sync"),
             S(60 * MS, 92 * MS, "cc.decode.run")]
    mods = {"/device:TPU:0": [(40 * MS, 50 * MS, "jit_prefill_window"),
                              (60 * MS, 64 * MS, "jit_paged_decode_step"),
                              (70 * MS, 76 * MS, "jit_paged_decode_step"),
                              (95 * MS, 110 * MS, "jit_paged_decode_step")]}
    return tr, PH.Phases(spans=spans, modules=mods)


def test_idle_time_goes_to_the_innermost_span():
    tr, ph = synthetic()
    by = PH.idle_by_span(tr, ph.spans)
    assert sum(by.values()) == pytest.approx(0.070)
    assert by["cc.prefill.compute"] == pytest.approx(0.010)
    assert by["cc.prefill.unpack"] == pytest.approx(0.018)
    assert by["bench.executor.prefill"] == pytest.approx(0.002)  # 38-40
    assert by["cc.decode.sync"] == pytest.approx(0.010)          # 50-60
    assert by["cc.decode.run"] == pytest.approx(0.030)
    assert set(by) == {"cc.prefill.compute", "cc.prefill.unpack",
                       "bench.executor.prefill", "cc.decode.sync",
                       "cc.decode.run"}
    assert PH.leaves(ph.spans) == {"cc.admit", "cc.prefill.compute",
                                   "cc.prefill.unpack", "cc.decode.sync",
                                   "cc.decode.run"}


def test_idle_per_request_per_step_and_module_time():
    tr, ph = synthetic()
    out = PH.report(tr, ph)
    assert out["idle_prefill_ms"] == pytest.approx(30.0 / 2)     # 10-40
    assert out["idle_decode_ms"] == pytest.approx(40.0)          # 50-90
    # the third step ends after the traced interval
    assert out["decode_step_device_ms"] == pytest.approx(5.0)
    assert out["modules"]["jit_paged_decode_step"][0] == 2
    assert out["idle_cc_leaf_share"] == pytest.approx(68 / 70)
    assert out["spans"]["cc.prefill"] == [1, pytest.approx(0.037)]


def test_gap_in_a_program_span_inside_a_benchmark_span_takes_its_name():
    tr, ph = synthetic()
    gaps = dict((round(d, 6), n) for n, d in PH.idle_gaps(tr, ph.spans))
    assert gaps[0.040] == "cc.decode.run"          # middle at 70 ms
    assert gaps[0.030] == "cc.prefill.unpack"      # middle at 25 ms
    # the benchmark's own reading names the same gaps by its spans
    bench = T.Trace(devices=tr.devices, window_s=tr.window_s, t0_ns=0,
                    t1_ns=100 * MS, spans=[(s.start, s.end, s.name)
                                           for s in ph.spans
                                           if s.name.startswith("bench.")])
    assert [d for _n, d in bench.idle_gaps()] == \
        [d for _n, d in PH.idle_gaps(tr, ph.spans)]


def test_no_spans_and_no_devices_read_as_nothing():
    tr, _ = synthetic()
    assert PH.idle_by_span(tr, []) == {PH.NO_SPAN: pytest.approx(0.070)}
    empty = T.Trace(devices={}, spans=[], window_s=0.1)
    out = PH.report(empty, PH.Phases(spans=[], modules={}))
    assert out["idle_s"] == 0 and out["idle_prefill_ms"] is None
    assert out["idle_decode_ms"] is None
    assert out["decode_step_device_ms"] is None


def test_modules_are_read_from_a_recorded_tpu_trace():
    """Two rounds of three jitted calls (``testdata/record_trace.py``)."""
    ph = PH.read(str(DATA))
    mods = ph.modules["/device:TPU:0"]
    assert len(mods) == 6
    assert sorted({n for _s, _e, n in mods}) == [
        "jit__lambda", "jit_chunk_attention", "jit_paged_decode_attention"]
    assert all(e > s for s, e, _n in mods)
    names = {s.name for s in ph.spans}
    assert {"bench.chunk_attention", "bench.sleep", T.WINDOW_SPAN} <= names


def _run(c0, c1, steps, prefills):
    snap = {"w0": {"decode_steps": 10, "prefills": 4, "counters": c0},
            "w1": {"decode_steps": 10 + steps, "prefills": 4 + prefills,
                   "counters": c1}}
    return RunData(dims=None, peak=None, seconds=51.0, w0=0.0, w1=51.0,
                   records=[], requests={}, prompts={}, snap=snap)


def test_host_sync_counter_readers():
    run = _run({"d2h_reads": 100, "d2h_bytes": 10**6},
               {"d2h_reads": 1800, "d2h_bytes": 10**6 + 6 * 10**8}, 100, 2)
    assert metric_reader("d2h_reads_per_step")(run) == pytest.approx(17.0)
    assert metric_reader("d2h_mb_per_req")(run) == pytest.approx(300.0)
    assert metric_reader("d2h_reads_per_step")(_run(
        {"d2h_reads": 1}, {"d2h_reads": 1}, 0, 0)) is None


def test_host_sync_counters_absent_read_as_nothing():
    """A program without the counters (the parent of the change that
    added them) gives no reading and does not fail."""
    run = _run({"paged_sync_bytes": 0}, {"paged_sync_bytes": 5}, 10, 1)
    assert metric_reader("d2h_reads_per_step")(run) is None
    assert metric_reader("d2h_mb_per_req")(run) is None


def test_traced_run_reads_the_program_spans_on_the_cpu():
    """The harness end to end at a tiny size: the program's spans reach
    the report, each decode step and prefill among them (the CPU has no
    device plane, so there is no idle time to name)."""
    import io
    from chipbench.tests.tiny import tiny_config, tiny_mix
    res, out = PH.traced_run("ds7b.rag_cold", 2**31 + 5, 3.0,
                             require_chip=False, compile_cache=False,
                             config=tiny_config(), mix=tiny_mix(),
                             out=io.StringIO())
    assert res["correct"], res["check"]
    spans = out["spans"]
    assert spans["cc.step"][0] > 0 and spans["cc.decode"][0] > 0
    assert spans["cc.prefill"][0] > 0 and spans["cc.dispatch"][0] > 0
    for name in ("cc.prefill.compute", "cc.decode.run", "cc.admit"):
        assert name in spans
    assert "d2h_reads_per_step" in res["metrics"]
    assert res["metrics"]["d2h_mb_per_req"]["value"] > 0
