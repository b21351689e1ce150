"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests that served tokens, drawn from the seed and always holding
the one with the most served tokens and the one with the longest prompt,
is run through the configuration's plain reference (prompt and served
tokens, from the first token).  A request still decoding when the run
ends counts with the tokens it had served: with outputs of 512-1536
tokens at tens of tokens a second, few requests of a closed-loop cell
finish inside one window.  At each position where the program emitted a
token, the reference's logit of that token is compared with the
reference's largest logit there.  The widest such gap is the number
compared; its limit is the configuration's ``check.max_logit_gap``.  A
greedy token of a sound program lies below the best only by the
program's own rounding; a wrong layer, page or token lies far below.

The sample also has to hold ``check.min_tokens`` served tokens, so that a
run that served little cannot pass by comparing little.
"""

from __future__ import annotations

from typing import List

import numpy as np

from bench.traffic import seeded_rng


def sample(served: list, n: int, seed: int) -> list:
    """``n`` requests: the one with the most served tokens, the one with
    the longest prompt (prefilled chunk by chunk across steps where it is
    longer than a chunk), then others in an order drawn from the seed."""
    if not served:
        return []
    ranked = sorted(served, key=lambda t: (-len(t.out_tokens), t.rid))
    first = [ranked[0]]
    longest = max(ranked, key=lambda t: (len(t.draw.prompt), -t.rid))
    if longest is not ranked[0] and n > 1:
        first.append(longest)
    rest = [t for t in ranked if all(t is not f for f in first)]
    order = seeded_rng(seed, 2).permutation(len(rest))
    return first + [rest[i] for i in order[:max(0, n - len(first))]]


def rows_of(picked: list):
    """Sequences (prompt + served tokens but the last) and the compared
    positions ``(sequence, position, served token)``."""
    seqs, rows = [], []
    for i, t in enumerate(picked):
        p = np.asarray(t.draw.prompt, np.int32)
        out = np.asarray(t.out_tokens, np.int32)
        seqs.append(np.concatenate([p, out[:-1]]))
        rows.extend((i, len(p) - 1 + j, int(out[j])) for j in range(len(out)))
    return seqs, rows


def compare(reference, config: dict, seed: int, picked: list, *,
            batch: int, control: bool = False):
    """The reference's rows at the served positions (see the reference's
    ``compare_rows``), over ``batch`` sequences of ``max_len`` tokens
    (fixed shapes: the reference compiles once per cell)."""
    seqs, rows = rows_of(picked)
    if not rows:
        empty = np.zeros(0, np.float32)
        return reference.Rows(empty, empty, empty)
    return reference.compare_rows(
        config, seed, seqs, rows, batch=batch,
        length=config["bench"]["serving"]["max_len"], control=control)


def checks(reference, rows, n_tokens: int, config: dict, *,
           control: bool = False) -> dict:
    """The numbers compared, each with its limit, in the order printed.
    With ``control`` the tokens judged are those the control puts first at
    the served positions: the control in the program's place."""
    lim = config["bench"]["check"]
    chosen = rows.ref_at_control if control else rows.ref_served
    return {
        "max_logit_gap": {"value": reference.widest_gap(rows.ref_max,
                                                        chosen),
                          "limit": lim["max_logit_gap"], "at_most": True},
        "served_tokens_compared": {"value": n_tokens,
                                   "limit": lim["min_tokens"],
                                   "at_most": False},
    }


def passed(c: dict) -> bool:
    """A number passes within its limit; a missing number or limit never
    passes (a configuration's limit stays unset until it is measured)."""
    v, lim = c["value"], c["limit"]
    if v is None or v != v or lim is None:
        return False
    return v <= lim if c["at_most"] else v >= lim


def served_requests(record) -> List:
    """Requests that served tokens and did not fail: finished by length,
    or still decoding when the run ended."""
    return [t for t in record.requests.values()
            if t.reason in ("length", None) and t.out_tokens]


def detail(picked: list, rows) -> dict:
    """Where the gaps lie, for the readings that set a limit: per sampled
    request its prompt length, served tokens and widest gap with the
    position it lies at, and over all positions the mean gap and the
    share of positions where the chosen token is not the reference's
    best, for the program and, where computed, the control."""
    _, where = rows_of(picked)
    seq = np.asarray([i for i, _, _ in where], np.int64)
    pos = np.asarray([p for _, p, _ in where], np.int64)
    out = {"requests": []}
    for name, at in (("program", rows.ref_served),
                     ("control", rows.ref_at_control)):
        gap = np.asarray(rows.ref_max - at, np.float64)
        if not len(gap) or np.isnan(gap).all():
            continue
        out[name] = {"mean": float(np.mean(gap)),
                     "off_best": float(np.mean(gap > 0)),
                     "p99": float(np.quantile(gap, 0.99))}
    for i, t in enumerate(picked):
        m = seq == i
        r = {"prompt": len(t.draw.prompt), "served": len(t.out_tokens)}
        for name, at in (("program", rows.ref_served),
                         ("control", rows.ref_at_control)):
            gap = np.asarray(rows.ref_max - at, np.float64)[m]
            if len(gap) and not np.isnan(gap).all():
                j = int(np.argmax(gap))
                r[name] = [float(gap[j]), int(pos[m][j])]
        out["requests"].append(r)
    return out


def describe(checks_: dict) -> List[str]:
    out = []
    for name, c in checks_.items():
        rel = "<=" if c["at_most"] else ">="
        out.append(f"[check] {name} {c['value']!r} (limit {rel} "
                   f"{c['limit']!r}) {'ok' if passed(c) else 'FAIL'}")
    return out


def summary(checks_: dict) -> dict:
    return {name: {"value": c["value"], "limit": c["limit"]}
            for name, c in checks_.items()}
