"""Every cell of BENCHMARK.json finds its files by name, and the file
keeps to the benchmark's contract."""
import json
import re
from pathlib import Path

import pytest

from chipbench import run as R

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_every_file_by_name(cell):
    c = R.load_cell(cell)
    assert c.config["name"] in {x["name"] for x in BENCH["configs"]}
    assert "arrival" in c.mix and "gap_max" in c.limits
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(R.metric_reader(m["name"]))
        assert m["moves"] in names


def test_contract_shape():
    assert set(BENCH) == KEYS
    assert BENCH["command"][:3] == ["python3", "-m", "chipbench.run"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    seen = set()
    for x in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(x["name"]) and x["name"] not in seen
        seen.add(x["name"])
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("chipbench/")
        assert conf["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert conf["published"][k] != conf[k]
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_configs_keep_published_widths():
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        for k in c["reduced"]:
            assert k in ("num_hidden_layers", "num_attention_heads",
                         "num_key_value_heads", "vocab_size"), k
