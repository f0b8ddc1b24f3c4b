"""Find the knee of an open-loop cell (a mix whose ``arrival`` is
``poisson``): one set-up, then one window per offered rate, each with
fresh requests of the cell's mix.

    python3 -m chipbench.sweep --workload <open-loop cell> --seed 5 \
        --rates 0.5,1,2,3 --seconds 25

For each rate it prints the offered and completed request rates, TTFT
p50/p90, ITL p90, output tokens/s and the backlog (requests due in the
window whose first token had not arrived when it closed). The knee is
the highest rate whose backlog stays near zero; a cell is set at about
four fifths of it.
"""
from __future__ import annotations

import argparse
import copy
import json
import math
import sys

from chipbench import stats
from chipbench.run import (ROOT, Generator, drive, load_cell, log, serve)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, ROOT)
    gen = Generator(cell.mix, int(cell.config["vocab_size"]), args.seed)
    sv = serve(cell, cell.config, cell.mix, gen, args.seed, ROOT, True)
    rows = []
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = copy.deepcopy(cell.mix)
            mix["arrival"]["rate_per_s"] = rate
            g = Generator(mix, int(cell.config["vocab_size"]), args.seed)
            reqs = g.window(args.seconds, round_=i + 1)
            win = drive(sv, reqs, mix, args.seconds, False)
            due = [r for r in win.records if win.w0 <= r.due < win.w1]
            done = [r for r in due if r.state == "done"]
            backlog = sum(1 for r in due if not r.token_times
                          or r.token_times[0] >= win.w1)
            ttft = stats.ttft_samples(win.records, win.w0, win.w1)
            itl = stats.itl_samples(win.records, win.w0, win.w1)
            row = dict(
                rate=rate, due=len(due), done=len(done), backlog=backlog,
                completed_per_s=sum(1 for r in win.records if r.token_times
                                    and win.w0 <= r.token_times[-1] < win.w1)
                / args.seconds,
                ttft_p50_ms=stats.percentile(ttft, 50) * 1e3,
                ttft_p90_ms=stats.percentile(ttft, 90) * 1e3,
                itl_p90_ms=stats.percentile(itl, 90) * 1e3 if itl else None,
                output_tok_s=stats.tokens_in(win.records, win.w0, win.w1)
                / args.seconds,
                decode_steps=win.snap["w1"]["decode_steps"]
                - win.snap["w0"]["decode_steps"])
            rows.append(row)
            log("sweep " + json.dumps(row))
    finally:
        sv.close()
    print(json.dumps({"sweep": rows},
                     default=lambda x: None if x != x or x in
                     (math.inf,) else x), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
