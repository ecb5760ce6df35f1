"""The `padded_rows` reader (bench/metrics/padded_rows.py) over hand-built
spans: its arithmetic, and its silence where the program's `serve.batch`
spans carry no `padded` statistic or are missing."""
import types

import pytest

from bench import run
from bench.spans import Span


def polls(padded):
    """A window of serving polls, 100 ns apart; `None` for a poll whose
    `serve.batch` carries no `padded` statistic."""
    out = []
    for k, rows in enumerate(padded):
        t0 = 100 * k
        stats = {"queries": 3, "wait_s_sum": 0.0, "wait_s_max": 0.0,
                 "ahead": 0}
        if rows is not None:
            stats["padded"] = rows
        out += [Span("bench.poll", t0, t0 + 90),
                Span("serve.batch", t0 + 1, t0 + 89, stats),
                Span("serve.forward", t0 + 20, t0 + 80)]
    return out


def value(found):
    return run.load_reader("padded_rows").value(found)


@pytest.mark.parametrize("padded, rows", [
    ([2048, 2048], 2048.0),
    ([256, 256, 512, 256], 320.0),
    ([8], 8.0),
])
def test_the_mean_rows_a_batch_computes(padded, rows):
    assert value(polls(padded)) == pytest.approx(rows)


@pytest.mark.parametrize("found", [
    [],
    [s for s in polls([256, 512]) if s.name == "bench.poll"],
    polls([None, None]),
    polls([256, None]),
], ids=["no_spans", "no_serve_batch", "no_statistic", "partly_missing"])
def test_a_program_without_the_statistic_reports_nothing(found):
    assert value(found) is None


def test_an_untraced_run_reports_nothing():
    assert run.load_reader("padded_rows").read(
        types.SimpleNamespace(summary=None)) is None
