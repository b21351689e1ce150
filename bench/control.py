#!/usr/bin/env python3
"""Readings that set the limit of the output check, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed, in one process: a run of the cell as ``bench/run.py`` makes
it, then the sample comparison over the same served tokens twice: once
for the program's tokens (its reading) and once for the control in the
program's place, the reference computed with float8 e4m3 matmul operands
(the precision below the configurations' bfloat16), which the check then
judges.  Prints one JSON line per seed with both widest gaps and the
control's ``correct``, which a sound limit makes false.  The limit lies
above every program reading and below every control reading (PERF.md
gives both and the limit).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    sys.path.insert(0, str(run.CHECKOUT / "src"))
    device = run.device_info(cell.chips)
    if device is None:
        return 2
    run.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed, args.seconds, False,
                           t_start=time.perf_counter(), device=device,
                           control=True)
        print(json.dumps({
            "seed": seed, "control_correct": res["correct"],
            "program": res["program_checks"]["max_logit_gap"]["value"],
            "control": res["checks"]["max_logit_gap"]["value"],
            "tokens": res["checks"]["served_tokens_compared"]["value"],
            "detail": res["gap_detail"],
            "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
