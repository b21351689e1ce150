"""The benchmark's yardstick: work counts, latency arithmetic, traffic, and
finding a cell's parts by name."""

import json
import shutil
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import cost, serve, spec, stats, traffic
from bench.spec import ROOT


def _shape(name):
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        return cost.shape(json.load(f))


# ---------------------------------------------------------------- work

def test_matmul_params_match_hand_counts():
    # smollm2-135m: q,o 576x576, k,v 576x192, MLP 3 x 576x1536
    s = _shape("smollm2-135m")
    assert cost.matmul_params_per_layer(s) == (
        2 * 576 * 576 + 2 * 576 * 192 + 3 * 576 * 1536)
    # with the tied 49152 x 576 embedding: the published 135M
    total = 30 * cost.matmul_params_per_layer(s) + 49152 * 576
    assert 134e6 < total < 136e6
    # olmo-1b: four 2048x2048 projections, MLP 3 x 2048x8192
    o = _shape("olmo-1b")
    assert cost.matmul_params_per_layer(o) == 4 * 2048 ** 2 + 3 * 2048 * 8192
    total = 16 * cost.matmul_params_per_layer(o) + 50304 * 2048
    assert 1.17e9 < total < 1.19e9


def test_attention_work_matches_hand_counts():
    s = _shape("smollm2-135m")
    # a decode row at position 99 sees 100 keys, 9 heads of 64
    assert cost.attention_flops(s, [(99, 1)]) == 4 * 100 * 9 * 64
    # its 100 keys lie in 7 pages of 16; K and V of 3 heads of 64 in
    # bf16, plus its query read and its output written
    assert cost.attention_bytes(s, [(99, 1)]) == (
        2 * 7 * 16 * 3 * 64 * 2 + 2 * 9 * 64 * 2)
    o = _shape("olmo-1b")
    # a 256-token chunk at 256..511: keys 257..512, 16 heads of 128
    keys = sum(range(257, 513))
    assert cost.attention_flops(o, [(256, 256)]) == 4 * keys * 16 * 128
    assert cost.attention_bytes(o, [(256, 256)]) == (
        2 * 32 * 16 * 16 * 128 * 2 + 2 * 256 * 16 * 128 * 2)
    # rows add
    assert cost.attention_flops(o, [(256, 256), (99, 1)]) == (
        cost.attention_flops(o, [(256, 256)])
        + cost.attention_flops(o, [(99, 1)]))


def test_least_time_takes_the_binding_bound():
    o = _shape("olmo-1b")
    pk = cost.peaks("TPU v5 lite")
    decode = [(1000, 1)] * 20
    f = cost.attention_flops(o, decode) / pk["flops_bf16"]
    b = cost.attention_bytes(o, decode) / pk["hbm_bytes_per_s"]
    assert b > f          # decode attention is bound by HBM
    assert cost.attention_least_s(o, decode, pk) == pytest.approx(16 * b)
    # model FLOPs: weights per fed token, attention, the head per emitted
    mf = cost.model_flops(o, decode, emitted=20)
    assert mf == pytest.approx(
        2 * 20 * 16 * cost.matmul_params_per_layer(o)
        + 16 * cost.attention_flops(o, decode) + 2 * 20 * 2048 * 50304)


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        cost.peaks("TPU v4")


# ---------------------------------------------------------- latency

def _record(token_times, dues, open_=10.0, close=20.0, closed=False):
    reqs = {}
    for i, (ts, due) in enumerate(zip(token_times, dues)):
        draw = traffic.Draw(i, np.zeros(4, np.int32), len(ts), 0.0)
        reqs[i] = serve.Tracked(draw=draw, due=due, rid=i, admitted=due,
                                times=list(ts), reason="length")
    return serve.Record(requests=reqs, steps=[], origin=0.0, open=open_,
                        close=close, end=close, stats_open={},
                        stats_close={}, compiles_in_window={},
                        closed_loop=closed)


def _steady(stall_at=None, stall_s=0.0):
    """Ten requests due 10.00..10.09, each with a first token 0.1 s after
    its due time and five more 0.05 s apart; a stall delays every token
    from ``stall_at`` on by ``stall_s``."""
    times, dues = [], []
    for i in range(10):
        due = 10.0 + 0.01 * i
        ts = [due + 0.1 + 0.05 * j for j in range(6)]
        if stall_at is not None:
            ts = [t + stall_s if t >= stall_at else t for t in ts]
        times.append(ts)
        dues.append(due)
    return _record(times, dues)


def test_percentiles_over_a_window():
    r = _steady()
    assert stats.percentile(stats.ttft_s(r), 90) == pytest.approx(0.1)
    assert stats.percentile(stats.itl_s(r), 50) == pytest.approx(0.05)
    assert stats.window_tokens(r) == 60
    assert stats.percentile([], 50) is None


def test_a_stall_moves_the_tail_and_not_the_median():
    calm, stalled = _steady(), _steady(stall_at=10.15, stall_s=2.0)
    # half the requests had their first token before the stall, half
    # wait through it; each of the first half has one gap across it
    assert stats.percentile(stats.itl_s(stalled), 50) == pytest.approx(
        stats.percentile(stats.itl_s(calm), 50))
    assert stats.percentile(stats.itl_s(stalled), 95) > 1.0
    assert stats.percentile(stats.ttft_s(calm), 90) == pytest.approx(0.1)
    assert stats.percentile(stats.ttft_s(stalled), 90) > 2.0
    # a stall past the close takes its tokens out of the window's rate
    late = _steady(stall_at=10.15, stall_s=10.0)
    assert stats.window_tokens(late) < stats.window_tokens(calm)


def test_only_gaps_ending_in_the_window_count():
    r = _record([[9.0, 9.5, 10.5, 21.0]], [8.0])
    assert stats.itl_s(r) == [pytest.approx(1.0)]
    assert stats.window_tokens(r) == 1
    assert stats.ttft_s(r) == []          # not due in the window


def test_attempted_and_failed():
    r = _steady()
    r.requests[0].times, r.requests[0].reason = [], None
    r.requests[1].reason = "rejected"
    assert stats.attempted_failed(r) == (10, 2)


# ---------------------------------------------------------- traffic

@pytest.mark.parametrize("name", ["chat-short", "decode-batch",
                                  "prompt-long"])
@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11, 2 ** 40 + 3])
def test_traffic_repeats_by_seed(name, seed):
    with open(ROOT / "bench" / "traffic" / f"{name}.json") as f:
        mix = json.load(f)
    head = traffic.head_start_count(mix, SLOTS)
    n = head + mix["block"] + 37

    def take(s):
        it = traffic.requests(mix, s, vocab=49152, max_len=2048, slots=SLOTS)
        return [next(it) for _ in range(n)]

    a, b, c = take(seed), take(seed), take(seed + 1)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and x.gap_s == y.gap_s for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    # stratified: one block offers the same sizes under every seed
    blk = slice(head, head + mix["block"])
    for key in (lambda d: len(d.prompt), lambda d: d.max_new):
        assert Counter(map(key, a[blk])) == Counter(map(key, c[blk]))
    assert sum(d.gap_s for d in a[blk]) == pytest.approx(
        sum(d.gap_s for d in c[blk]))
    for d in a[head:]:
        assert mix_bounds(mix["prompt"], 2048)[0] <= len(d.prompt) \
            <= mix_bounds(mix["prompt"], 2048)[1]
        assert d.prompt.max() < 49152


SLOTS = 20


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 7])
def test_a_head_start_is_the_same_part_served_set_under_every_seed(seed):
    """The first ``per_slot * slots`` requests arrive with a uniform share
    of their output served and folded into the prompt; every seed starts
    from the same set of (prompt, output, served) sizes, in another order,
    and the requests after them are drawn as without a head start."""
    with open(ROOT / "bench" / "traffic" / "decode-batch.json") as f:
        mix = json.load(f)
    head = traffic.head_start_count(mix, SLOTS)
    assert head == SLOTS * mix["head_start"]["per_slot"] > 0
    lo_p, hi_p = mix_bounds(mix["prompt"], 2048)
    lo_o, hi_o = mix_bounds(mix["output"], 2048)

    def take(s):
        it = traffic.requests(mix, s, vocab=49152, max_len=2048, slots=SLOTS)
        return [next(it) for _ in range(head + 3)]

    a, c = take(seed), take(seed + 1)
    assert [len(d.prompt) for d in a] != [len(d.prompt) for d in c]
    assert (Counter((len(d.prompt), d.max_new) for d in a[:head])
            == Counter((len(d.prompt), d.max_new) for d in c[:head]))
    served = []
    for d in a[:head]:
        total = len(d.prompt) + d.max_new          # prompt + whole output
        assert lo_p + lo_o <= total <= hi_p + hi_o
        assert d.max_new >= 1 and d.gap_s == 0.0
        served.append(len(d.prompt) - lo_p)
    # shares spread over [0, 1): some start fresh, some near their end
    assert min(served) <= hi_p - lo_p and max(served) > hi_o // 2
    plain = dict(mix)
    del plain["head_start"]
    rest = traffic.requests(plain, seed, vocab=49152, max_len=2048)
    firsts = [next(rest) for _ in range(3)]
    assert [d.max_new for d in a[head:]] == [d.max_new for d in firsts]


def mix_bounds(dist, max_len):
    scale = max_len if dist.get("scale") == "max_len" else 1
    return dist["min"] * scale, dist["max"] * scale


def test_every_request_fits_its_configuration():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.find_cell(w["name"])
        ml = cell.config["bench"]["serving"]["max_len"]
        p = mix_bounds(cell.traffic["prompt"], ml)[1]
        o = mix_bounds(cell.traffic["output"], ml)[1]
        assert p + o - 1 <= ml, w["name"]


# ----------------------------------------------------- cells by name

def test_a_cell_of_new_files_is_found_by_name(tmp_path):
    """A later change adds a configuration, a mix, a cell and a metric as
    files of their own and entries in BENCHMARK.json, and edits none."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "olmo-1b.json").read_text())
    cfg["bench"]["serving"]["slots"] = 8
    (b / "configs" / "new-model.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "chat-short.json").read_text())
    mix["arrivals"]["rate_per_s"] = 0.5
    (b / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (b / "metrics" / "new_metric.py").write_text(
        "def read(obs):\n    return 42.0\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "new-model", "source": "x",
                             "file": "bench/configs/new-model.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-model.new-mix",
                               "config": "new-model", "traffic": "new-mix",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric.online", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "device", "moves": "setup_s",
                               "workloads": ["new-model.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.find_cell("new-model.new-mix", tmp_path)
    assert cell.config["bench"]["serving"]["slots"] == 8
    assert cell.traffic["arrivals"]["rate_per_s"] == 0.5
    assert [m["name"] for m in cell.per_layer] == ["new_metric.online"]
    assert {m["name"] for m in cell.end_to_end} == {"setup_s"}
    # found by its full name's base, as mfu.online finds mfu.py
    assert spec.metric_reader(cell, "new_metric.online")(None) == 42.0
    assert spec.reference(cell).dims(cell.config).d_model == 2048
    with pytest.raises(KeyError):
        spec.find_cell("no-such.cell", tmp_path)


def test_every_metric_of_every_cell_has_a_reader():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.find_cell(w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(cell, m["name"]))


def test_no_tpu_means_no_result(capsys):
    """On a machine without a TPU the run exits non-zero and prints no
    result line; it never falls back to the CPU."""
    from bench import run
    cell = spec.load_benchmark()["workloads"][0]["name"]
    assert run.main(["--workload", cell, "--seed", "1",
                     "--seconds", "1"]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs 1 TPU chip" in out.err


def test_benchmark_json_is_well_formed():
    import re
    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    names = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and 0 < len(c["why"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert all(name.match(k) for k in c["reduced"])
    cells = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
        cells.add(w["name"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["name"] not in names
        names.add(m["name"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and 0 < len(m["layer"]) <= 200
        moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))


def test_the_check_samples_the_longest_output_and_the_longest_prompt():
    from bench import check

    def t(rid, prompt, out):
        return serve.Tracked(draw=traffic.Draw(rid, np.zeros(prompt, np.int32),
                                               out, 0.0),
                             due=0.0, rid=rid, out_tokens=[1] * out)

    served = [t(0, 10, 50), t(1, 700, 5), t(2, 20, 9), t(3, 30, 8),
              t(4, 40, 7), t(5, 50, 6)]
    for seed in (1, 2 ** 33 + 1):
        picked = check.sample(served, 4, seed)
        assert [p.rid for p in picked[:2]] == [0, 1]
        assert len({p.rid for p in picked}) == 4
    assert [p.rid for p in check.sample(served, 1, 3)] == [0]
    assert check.sample(served, 4, 5) == check.sample(served, 4, 5)


def test_the_gap_detail_places_each_request_s_widest_gap():
    from bench import check
    from bench.references.dense_decoder import Rows

    def t(rid, prompt, out):
        return serve.Tracked(draw=traffic.Draw(rid, np.zeros(prompt, np.int32),
                                               out, 0.0),
                             due=0.0, rid=rid, out_tokens=[1] * out)

    picked = [t(0, 4, 3), t(1, 6, 2)]
    ref_max = np.full(5, 2.0, np.float32)
    served = np.array([2.0, 1.5, 2.0, 2.0, 1.0], np.float32)
    rows = Rows(ref_max, served, np.full(5, np.nan, np.float32))
    d = check.detail(picked, rows)
    assert d["requests"] == [{"prompt": 4, "served": 3, "program": [0.5, 4]},
                             {"prompt": 6, "served": 2, "program": [1.0, 6]}]
    assert d["program"]["off_best"] == 0.4
    assert abs(d["program"]["mean"] - 0.3) < 1e-9
    assert "control" not in d
