#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix.  The run makes the configuration's weights on the device from
the seed, builds the serving engine (``build_model`` -> ``Engine`` with the
flat ``[1, W]`` step), compiles or loads every flat width it will use, runs
the mix's ramp, and serves the mix for ``--seconds``.  It then reads the
device's peak memory, frees the program's state, and runs the sample
comparison against the configuration's reference (``bench/check.py``).

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the profiler traces the window's first ``TRACE_S``
seconds and the metrics are the cell's per-layer metrics, each read by its
own file under ``bench/metrics``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``) and, last, ``checks``: each number compared with its limit,
which are also the last lines of standard error.  A machine without a TPU,
or with fewer chips than the cell asks for, ends the run with exit code 2
and no result.

JAX's persistent compilation cache lives in ``.jax_cache`` at the root of
the checkout unless ``JAX_COMPILATION_CACHE_DIR`` places it.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, List, Optional

CHECKOUT = Path(__file__).resolve().parents[1]
if str(CHECKOUT) not in sys.path:
    sys.path.insert(0, str(CHECKOUT))

from bench import check, cost, serve, spec, stats, xtrace  # noqa: E402

TRACE_S = 5.0
TRACE_DIR = CHECKOUT / ".bench_trace"


@dataclasses.dataclass
class Observation:
    """What a metric reader reads."""
    cell: spec.Cell
    record: serve.Record
    setup_s: float
    shape: cost.Shape
    peaks: Optional[dict]
    trace: Optional[xtrace.Trace] = None
    _spans: Optional[list] = None
    _busy: Optional[list] = None

    def window_steps(self) -> List[serve.Step]:
        r = self.record
        return [s for s in r.steps if r.open <= s.t1 < r.close]

    def traced_steps(self) -> List[serve.Step]:
        return [s for s in self.record.steps if s.traced]

    def step_spans(self) -> list:
        """The traced steps' ``bench.step`` spans (ns), in order."""
        if self.trace is None:
            return []
        if self._spans is None:
            self._spans = xtrace.spans(self.trace, "bench.step")
            n = len(self.traced_steps())
            if len(self._spans) != n:
                raise RuntimeError(f"trace holds {len(self._spans)} step "
                                   f"spans for {n} traced steps")
        return self._spans

    def step_busy_ns(self) -> list:
        if self._busy is None:
            self._busy = xtrace.busy_in(self.trace.device, self.step_spans())
        return self._busy

    def device_time_in(self, spans, match: Callable[[str], bool]) -> float:
        return xtrace.time_in(self.trace.device, spans, match)


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> None:
    import os
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_info(chips: int, require_tpu: bool = True) -> Optional[dict]:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and (info["platform"] != "tpu" or info["count"] < chips):
        _log(f"[device] {info}: this cell needs {chips} TPU chip(s)")
        return None
    return info


def build_model(cell: spec.Cell, seed: int):
    """The configuration's model and its weights, made on the device."""
    from repro.configs.base import ModelConfig, RunConfig, ShapeSpec
    from repro.models.model import build_model as build

    from bench import weights
    b = cell.config["bench"]
    sv = b["serving"]
    run_cfg = RunConfig(param_dtype=b["dtype"], compute_dtype=b["dtype"],
                        remat=False)
    model = build(ModelConfig(**b["model"]), run_cfg,
                  ShapeSpec("serve", sv["max_len"], sv["slots"], "decode"))
    return model, weights.program_params(model, seed, b["dtype"])


def make_engine(cell: spec.Cell, model, params):
    """The serving engine with the configuration's settings, warmed up."""
    import jax
    from repro.serving.engine import Engine
    sv = cell.config["bench"]["serving"]
    engine = Engine(model, params, max_slots=sv["slots"],
                    page_tokens=sv["page_tokens"],
                    chunk_tokens=sv["chunk_tokens"],
                    token_budget=sv["token_budget"])
    engine.warmup()
    jax.block_until_ready(engine.caches)
    return engine


def _memory_peak() -> Optional[int]:
    import jax
    stats = jax.devices()[0].memory_stats()
    return int(stats["peak_bytes_in_use"]) if stats else None


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, device: dict, trace_dir: Path = TRACE_DIR,
             control: bool = False) -> dict:
    """Serve the cell, read its metrics and check its outputs.  With
    ``control`` the check judges the control in the program's place: the
    reference computed in the precision below the configuration's, at the
    same served positions (``bench/control.py``); the program's own
    numbers then go under ``program_checks``.  The benchmark's own runs
    never compute the control."""
    model, params = build_model(cell, seed)
    engine = make_engine(cell, model, params)
    del model, params     # the engine serves its prepacked copy
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    record = serve.run(engine, cell, seed, seconds,
                       trace_dir=trace_dir if trace else None,
                       trace_s=TRACE_S if trace else 0.0)
    setup_s = record.open - t_start
    if any(record.compiles_in_window.values()):
        raise RuntimeError(f"the window compiled: {record.compiles_in_window}")
    device = dict(device, memory_peak_bytes=_memory_peak())
    _log(f"[memory] peak_bytes_in_use {device['memory_peak_bytes']}")
    _log(f"[window] {len(record.steps)} steps, open {record.open - t_start!r}"
         f" s after start, generator at most {record.late_max_s!r} s late, "
         f"stats at close {record.stats_close.get('flat')}")

    del engine
    gc.collect()

    obs = Observation(cell=cell, record=record, setup_s=setup_s,
                      shape=cost.shape(cell.config),
                      peaks=(cost.peaks(device["kind"])
                             if device["platform"] == "tpu" else None))
    result_extra = {}
    if trace:
        obs.trace = xtrace.load(xtrace.find_xplane(str(trace_dir)))
        win = xtrace.spans(obs.trace, "bench.window")
        if win:
            lo, hi = win[0]
            device["busy_s"] = xtrace.union_within(
                [(o.start, o.end) for o in obs.trace.device], lo, hi) * 1e-9
            device["window_s"] = (hi - lo) * 1e-9
            result_extra["breakdown"] = {
                "device_ops": xtrace.top_ops(obs.trace.device, lo, hi),
                "idle_gaps": xtrace.idle_gaps(obs.trace, lo, hi)}
            _log(f"[trace] planes {obs.trace.planes}; "
                 f"{len(obs.trace.device)} device ops, busy "
                 f"{device['busy_s']!r} s of {device['window_s']!r} s")
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(cell, m["name"])(obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    attempted, failed = stats.attempted_failed(record)

    ref = spec.reference(cell)
    n_check = int(cell.traffic["check"]["requests"])
    picked = check.sample(check.served_requests(record), n_check, seed)
    t_ref = time.perf_counter()
    rows = check.compare(ref, cell.config, seed, picked, batch=n_check,
                         control=control)
    n_tok = sum(len(t.out_tokens) for t in picked)
    checks = check.checks(ref, rows, n_tok, cell.config)
    if control:
        result_extra["program_checks"] = check.summary(checks)
        result_extra["gap_detail"] = check.detail(picked, rows)
        checks = check.checks(ref, rows, n_tok, cell.config, control=True)
    _log(f"[check] {len(picked)} requests, {n_tok} served tokens compared "
         f"in {time.perf_counter() - t_ref!r} s")
    result = {"correct": all(check.passed(c) for c in checks.values()),
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device, **result_extra,
              "checks": check.summary(checks)}
    for line in check.describe(checks):
        _log(line)
    return result


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.find_cell(args.workload)
    sys.path.insert(0, str(CHECKOUT / "src"))
    device = device_info(cell.chips)
    if device is None:
        return 2
    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start, device=device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
