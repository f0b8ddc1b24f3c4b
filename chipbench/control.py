"""The output check's two readings on the chip, at a cell's own size.

    python3 -m chipbench.control --workload ds7b.rag_cold \
        --seeds 11,12,13 --seconds 20

For each seed, one run of the cell (fresh weights and traffic from the
seed) in this one process. After its window the output check is made
twice over the same sample: once on the program's served tokens, and
once with the control in the program's place (the same reference with
fp8 matmul operands, read at the same positions: the gap of the token
that fp8 puts first). Both are judged by the cell's limits through the
same path as a benchmark run: the program's check has to pass and the
control's has to fail. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import io
import json
import sys

from chipbench.run import passes, run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        readings = {}
        res = run_cell(args.workload, seed, args.seconds, False,
                       control=True, readings=readings, out=io.StringIO())
        prog = readings["program"]
        row = {"seed": seed,
               "program": {k: v["value"] for k, v in prog.items()},
               "program_correct": passes(prog),
               "control": {k: v["value"] for k, v in res["check"].items()},
               "control_correct": res["correct"],
               "limits": {k: v["limit"] for k, v in prog.items()},
               "attempted": res["attempted"], "failed": res["failed"]}
        rows.append(row)
        print("control " + json.dumps(row), flush=True)
    print(json.dumps({"readings": rows}), flush=True)
    ok = all(r["program_correct"] and not r["control_correct"] for r in rows)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
