"""The control at a size a test run can hold: the plain reference with
fp8 matmul operands in the program's place, judged by the cell's limits
through the same check as the program's own run of the same seed. On
the CPU the program's matmuls run in float32 like the reference's, so
the program's reading is at the rounding floor; the control's lies
above the limits. The chip readings and the limits set from them are in
PERF.md."""
import io

from chipbench import run as R
from chipbench.tests.tiny import tiny_config, tiny_mix


def test_control_is_not_correct_where_the_program_is():
    c = tiny_config()
    c.update(hidden_size=256, num_attention_heads=4, num_key_value_heads=4,
             head_dim=64, intermediate_size=512, vocab_size=8192)
    readings = {}
    res = R.run_cell("ds7b.rag_cold", 2**31 + 29, 3.0, False,
                     require_chip=False, compile_cache=False, config=c,
                     mix=tiny_mix("rag_cold"), control=True,
                     readings=readings, out=io.StringIO())
    program = readings["program"]
    assert R.passes(program)
    assert program["gap_max"]["value"] <= 1e-3
    assert not res["correct"]
    assert res["check"]["gap_max"]["value"] > res["check"]["gap_max"]["limit"]
    assert list(res)[-1] == "check"
    assert not any(k.startswith("control") for k in res)
