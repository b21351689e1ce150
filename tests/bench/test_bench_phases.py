"""The readers of the engine's step phases (bench/phases.py and the
metrics that use it) on a small trace recorded on the CPU.

The trace holds a one-layer smollm2 flat engine draining three requests,
each ``Engine.step`` inside a ``bench.step`` span and all inside one
``bench.window``: steps that prefill and steps that only decode.  On the
CPU the XLA operations are host events with an ``hlo_op`` stat; the tests
count those as the device's operations.  Recorded with

    JAX_PLATFORMS=cpu PYTHONPATH=src python tests/bench/test_bench_phases.py
"""

import shutil
import sys
from pathlib import Path

import pytest

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import phases, run, spec, xtrace  # noqa: E402

DATA = Path(__file__).parent / "data"
TRACE = DATA / "engine_trace.xplane.pb"
NO_ENGINE_SPANS = DATA / "cpu_trace.xplane.pb"
READERS = ["schedule_ms_per_step", "logits_to_host_ms_per_step",
           "commit_ms_per_step", "decode_step_device_ms"]
PHASE_OF = {"schedule_ms_per_step": "engine.schedule",
            "logits_to_host_ms_per_step": "engine.logits_to_host",
            "commit_ms_per_step": "engine.commit"}


def cpu_ops(plane, line, event):
    return plane == "/host:CPU" and any(k == "hlo_op" for k, _ in event.stats)


def _observe(path, tmp_path, monkeypatch):
    """An observation of ``path``, laid where the harness leaves a traced
    run's trace."""
    where = tmp_path / "trace" / "plugins" / "profile" / "1"
    where.mkdir(parents=True)
    shutil.copy(path, where / "host.xplane.pb")
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    return run.Observation(cell=None, record=None, setup_s=0.0, shape=None,
                           peaks=None, trace=xtrace.load(str(path), cpu_ops))


def _reader(name, cell="smollm2-135m.chat-short", suffix="online"):
    return spec.metric_reader(spec.find_cell(cell), f"{name}.{suffix}")


@pytest.fixture
def obs(tmp_path, monkeypatch):
    return _observe(TRACE, tmp_path, monkeypatch)


@pytest.mark.parametrize("name", READERS)
def test_each_reader_reads_a_positive_value(obs, name, capsys):
    assert _reader(name)(obs) > 0
    assert _reader(name, "olmo-1b.decode-batch", "offline")(obs) > 0
    # the coverage line is printed once per observation
    assert capsys.readouterr().err.count("[trace] engine spans:") == 1


@pytest.mark.parametrize("name", READERS)
def test_each_phase_mean_is_at_most_the_mean_bench_step(obs, name):
    bench = xtrace.spans(obs.trace, "bench.step")
    mean_ns = sum(e - s for s, e in bench) / len(bench)
    assert _reader(name)(obs) <= mean_ns * 1e-6
    if name in PHASE_OF:        # the mean over steps, not over spans
        spans = xtrace.spans(obs.trace, PHASE_OF[name])
        assert _reader(name)(obs) == pytest.approx(
            sum(e - s for s, e in spans) / len(bench) * 1e-6)


def test_steps_hold_their_six_phases_in_order(obs):
    steps = phases.steps(obs)
    assert len(steps) == len(xtrace.spans(obs.trace, "bench.step")) > 4
    for st in steps:
        assert [o.name for o in st.children] == list(phases.CHILDREN)
        for a, b in zip(st.children, st.children[1:]):
            assert a.end <= b.start
        assert st.start <= st.children[0].start
        assert st.children[-1].end <= st.end


def test_decode_selection_keeps_the_steps_without_prefill(obs):
    """Against the dispatch stats read straight from the file."""
    from jax.profiler import ProfileData
    want = []
    for plane in ProfileData.from_file(str(TRACE)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "engine.dispatch":
                    args = dict(ev.stats)
                    assert args["prefill_tokens"] + args["decode_tokens"] \
                        <= args["width"]
                    if args["prefill_tokens"] == 0:
                        want.append(args["step"])
    got = [s.args["step"] for s in phases.decode_steps(obs)]
    assert got == sorted(want)
    # both kinds of step are in the trace
    assert 0 < len(got) < len(phases.steps(obs))
    spans = [(s.start, s.end) for s in phases.decode_steps(obs)]
    busy = xtrace.busy_in(obs.trace.device, spans)
    assert _reader("decode_step_device_ms")(obs) == pytest.approx(
        sum(busy) / len(busy) * 1e-6)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_engine_spans_reads_nothing(tmp_path, monkeypatch,
                                                      name):
    """The parent of these spans: its traced run reports no value."""
    obs = _observe(NO_ENGINE_SPANS, tmp_path, monkeypatch)
    assert xtrace.spans(obs.trace, "bench.step")
    assert _reader(name)(obs) is None


def record(path: Path) -> None:
    import tempfile

    import jax
    import numpy as np
    from repro.configs import RunConfig, ShapeSpec, get_config, \
        reduced_config
    from repro.models.model import build_model
    from repro.serving.engine import Engine

    cfg = reduced_config(get_config("smollm2-135m"), layers=1)
    model = build_model(cfg, RunConfig(param_dtype="float32",
                                       compute_dtype="float32", remat=False),
                        ShapeSpec("serve", 64, 3, "decode"))
    engine = Engine(model, model.init(jax.random.PRNGKey(0)), max_slots=3,
                    page_tokens=8, chunk_tokens=8, token_budget=16)
    engine.warmup()
    for n, new in [(13, 4), (21, 3), (5, 6)]:
        engine.add_request(np.arange(1, n + 1) % cfg.vocab, new)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            while engine.scheduler.has_work:
                with jax.profiler.TraceAnnotation("bench.step"):
                    engine.step()
        jax.profiler.stop_trace()
        shutil.copy(xtrace.find_xplane(tmp), path)


if __name__ == "__main__":
    record(TRACE)
