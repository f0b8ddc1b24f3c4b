"""The pool-twin write counter's reader: ``twin_write_bytes`` per
request prefilled in the window, and nothing where the program lacks
the counter."""
import pytest

from chipbench.run import RunData, metric_reader


def _run(c0, c1, prefills):
    snap = {"w0": {"decode_steps": 10, "prefills": 4, "counters": c0},
            "w1": {"decode_steps": 60, "prefills": 4 + prefills,
                   "counters": c1}}
    return RunData(dims=None, peak=None, seconds=51.0, w0=0.0, w1=51.0,
                   records=[], requests={}, prompts={}, snap=snap)


def test_twin_write_counter_reader():
    read = metric_reader("twin_write_mb_per_req")
    run = _run({"twin_write_bytes": 10**6},
               {"twin_write_bytes": 10**6 + 3 * 182 * 10**6}, 3)
    assert read(run) == pytest.approx(182.0)
    assert read(_run({"twin_write_bytes": 0}, {"twin_write_bytes": 0},
                     0)) is None


def test_twin_write_counter_absent_reads_as_nothing():
    """A program without the counter (the parent of the change that
    added it) gives no reading and does not fail."""
    run = _run({"paged_sync_bytes": 0}, {"paged_sync_bytes": 5}, 1)
    assert metric_reader("twin_write_mb_per_req")(run) is None
