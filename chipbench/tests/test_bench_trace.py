"""Trace reduction: device busy time as the union of operation
intervals, idle gaps named by the benchmark's spans, kernel time by
name, and roofline shares from it."""
import pytest

from chipbench import trace as T
from chipbench.flops import Dims, paged_decode_work, roofline_seconds
from chipbench.peaks import peak_for


def synthetic():
    ms = 1_000_000
    ops = [T.Op(0 * ms, 10 * ms, "fusion.1", "fusion.1"),
           T.Op(5 * ms, 12 * ms, "custom-call.2", "custom-call.2 _paged_kernel"),
           T.Op(40 * ms, 50 * ms, "fusion.1", "fusion.1"),
           T.Op(90 * ms, 100 * ms, "custom-call.3", "custom-call.3 _kernel")]
    spans = [(0, 100 * ms, "bench.engine.step"),
             (15 * ms, 35 * ms, "bench.store.get_kv")]
    return T.Trace(devices={"/device:TPU:0": ops}, spans=spans,
                   window_s=0.1, t0_ns=0, t1_ns=100 * ms)


def test_busy_is_the_union_of_operation_intervals():
    tr = synthetic()
    assert tr.busy_s() == pytest.approx(0.032)
    assert 1 - tr.busy_s() / tr.window_s == pytest.approx(0.68)


def test_idle_gaps_longest_first_named_by_the_innermost_span():
    gaps = synthetic().idle_gaps()
    assert gaps[0] == ("bench.engine.step", pytest.approx(0.040))
    assert gaps[1] == ("bench.store.get_kv", pytest.approx(0.028))
    assert len(gaps) == 2


def test_kernel_time_by_name_and_top_ops():
    tr = synthetic()
    assert tr.op_seconds(r"_paged_kernel") == pytest.approx(0.007)
    assert tr.op_seconds(r"(?<![a-z])_kernel(?![a-z_])") == \
        pytest.approx(0.010)
    top = tr.top_ops(2)
    assert top[0] == ("fusion.1", pytest.approx(0.020))


def test_roofline_share_of_a_memory_bound_kernel():
    m = Dims(layers=4, d_model=4096, heads=32, kv_heads=32, head_dim=128,
             d_ff=11008, vocab=102400)
    fl, by = paged_decode_work(m, 2000)
    least = roofline_seconds(fl, by, peak_for("TPU v5 lite"))
    assert least == pytest.approx(by / 819e9)   # bound by bandwidth
    assert by == 4 * 4 * (2 * 2000 * 32 * 128 + 2 * 32 * 128)


DATA = __import__("pathlib").Path(__file__).resolve().parents[1] / \
    "testdata" / "tpu_small.xplane.pb"
CHUNK = r'^(?!%?paged_decode_attention)[^=]*=.*"tpu_custom_call"'


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e by ``testdata/record_trace.py``:
    two rounds of the chunk-attention kernel, the paged decode kernel and
    a matmul, 50 ms of sleep between them."""
    tr = T.read(str(DATA), window_s=0.0)
    assert list(tr.devices) == ["/device:TPU:0"]
    assert 0.05 < tr.window_s < 0.2
    busy = tr.busy_s()
    assert 0 < busy < 0.01 < tr.window_s
    gaps = tr.idle_gaps()
    assert gaps[0][0] == "bench.sleep" and 0.045 < gaps[0][1] < 0.06
    chunk, paged = tr.op_seconds(CHUNK), tr.op_seconds(r"paged_decode")
    assert chunk > 0 and paged > 0 and chunk + paged <= busy
    from chipbench.metrics.chunk_attn_roofline import KERNEL
    assert tr.op_seconds(KERNEL) == chunk
