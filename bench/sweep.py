#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell sustains, on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 2,3,4,...

Serves the cell's traffic at each rate in turn, in one process (the
compiled steps are shared), each on a fresh engine, and prints one JSON
line per rate: requests due in the window, the share of them admitted by
the window's close, the backlog at the close, time to first token and the
gaps between tokens.  A rate is sustained while the backlog stays near
zero and the first-token tail stays flat; the cell's rate is set to about
0.8 of the highest such rate, by hand, in its traffic file.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run, serve, spec, stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    sys.path.insert(0, str(run.CHECKOUT / "src"))
    if run.device_info(cell.chips) is None:
        return 2
    run.enable_compile_cache()
    model, params = run.build_model(cell, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        c = copy.deepcopy(cell)
        c.traffic["arrivals"]["rate_per_s"] = rate
        engine = run.make_engine(c, model, params)
        t0 = time.perf_counter()
        rec = serve.run(engine, c, args.seed, args.seconds)
        due = stats.due_in_window(rec)
        admitted = [t for t in due
                    if t.admitted is not None and t.admitted < rec.close]
        ttft, itl = stats.ttft_s(rec), stats.itl_s(rec)
        print(json.dumps({
            "rate": rate, "due": len(due),
            "admitted_by_close": len(admitted) / max(1, len(due)),
            "backlog_at_close": len(due) - len(admitted),
            "ttft_p50_ms": 1e3 * (stats.percentile(ttft, 50) or 0),
            "ttft_p90_ms": 1e3 * (stats.percentile(ttft, 90) or 0),
            "itl_p50_ms": 1e3 * (stats.percentile(itl, 50) or 0),
            "itl_p95_ms": 1e3 * (stats.percentile(itl, 95) or 0),
            "tokens_per_s": stats.window_tokens(rec) / (rec.close - rec.open),
            "steps": len(rec.steps), "wall_s": time.perf_counter() - t0}),
            flush=True)
        del engine, rec
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
