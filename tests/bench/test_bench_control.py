"""The output check against its control and against planted faults, at a
size the CPU holds: a two-layer bf16 decoder served through the whole of
``run_cell`` (the harness's look for a chip skipped).

Readings that set the tiny cell's limit of 0.015 (CPU, seeds 1-3): the
program's widest gap 0 to 0.0031; the control's (the reference with
float8 e4m3 matmul operands) 0.041 to 0.086.
"""

import time

import numpy as np
import pytest

import bench_tiny
from bench import run, spec

LIMIT = 0.015


def _run(tmp_path, seed, *, norm="rmsnorm", control=False):
    root = bench_tiny.make_root(tmp_path, norm=norm, limit=LIMIT)
    return _run_root(root, seed, control=control)


def _run_root(root, seed, *, control=False):
    cell = spec.find_cell("tiny.mix", root)
    device = run.device_info(1, require_tpu=False)
    return run.run_cell(cell, seed, 1.0, False, t_start=time.perf_counter(),
                        device=device, trace_dir=root / "trace",
                        control=control)


@pytest.mark.parametrize("norm,seed", [("rmsnorm", 2 ** 31 + 5),
                                       ("layernorm_np", 3)])
def test_control_fails_where_the_program_passes(tmp_path, norm, seed):
    """The control in the program's place comes out as not correct, on
    the same served positions where the program's own tokens pass."""
    res = _run(tmp_path, seed, norm=norm, control=True)
    assert res["program_checks"]["max_logit_gap"]["value"] <= LIMIT
    assert res["checks"]["max_logit_gap"]["value"] > LIMIT
    assert not res["correct"]
    assert list(res)[-1] == "checks"


def test_an_altered_token_is_caught(tmp_path, monkeypatch):
    """A token altered where it is produced: every third pick of the
    engine returns the runner-up instead of the argmax."""
    from repro.serving.engine import Engine
    pick = Engine._pick
    calls = [0]

    def altered(self, logits_row, req, greedy, seed):
        calls[0] += 1
        if calls[0] % 3:
            return pick(self, logits_row, req, greedy, seed)
        return int(np.argsort(np.asarray(logits_row))[-2])

    monkeypatch.setattr(Engine, "_pick", altered)
    res = _run(tmp_path, 11)
    assert calls[0] > 0
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > LIMIT


def test_a_run_that_serves_too_little_is_not_correct(tmp_path, monkeypatch):
    """Nothing served means nothing compared, which never passes."""
    from bench import check
    monkeypatch.setattr(check, "served_requests", lambda record: [])
    res = _run(tmp_path, 12)
    assert not res["correct"]
    assert res["checks"]["served_tokens_compared"]["value"] == 0


def test_a_head_start_ramp_ends_once_every_head_request_decodes(tmp_path):
    """A closed loop with a head start opens its window only when each
    part-served request has its first token, and checks as any run."""
    from bench import serve
    root = bench_tiny.make_root(tmp_path, closed=True, head_start=True)
    cell = spec.find_cell("tiny.mix", root)
    model, params = run.build_model(cell, 21)
    engine = run.make_engine(cell, model, params)
    rec = serve.run(engine, cell, 21, 0.5)
    head = sorted(rec.requests.values(), key=lambda t: t.rid)[:engine.slots]
    assert all(t.times and t.times[0] <= rec.open for t in head)
    assert rec.open < rec.close and rec.steps
    res = _run_root(root, 21)
    assert res["correct"], res["checks"]


def test_the_result_line_ends_with_the_checks(tmp_path):
    res = _run(tmp_path, 13)
    assert list(res)[-1] == "checks"
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert set(res["checks"]) == {"max_logit_gap", "served_tokens_compared"}
    assert res["metrics"]["setup_s"]["value"] > 0
