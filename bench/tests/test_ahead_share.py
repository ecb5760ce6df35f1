"""The `ahead_share` reader (bench/metrics/ahead_share.py) over hand-built
spans: its arithmetic, and its silence where the program's `serve.batch`
spans carry no `ahead` statistic or are missing."""
import types

import pytest

from bench import run
from bench.spans import Span


def polls(aheads):
    """A window of serving polls, 100 ns apart; `None` for a poll whose
    `serve.batch` carries no `ahead` statistic."""
    out = []
    for k, ahead in enumerate(aheads):
        t0 = 100 * k
        stats = {"queries": 8, "padded": 8, "wait_s_sum": 0.0,
                 "wait_s_max": 0.0}
        if ahead is not None:
            stats["ahead"] = ahead
        out += [Span("bench.poll", t0, t0 + 90),
                Span("serve.batch", t0 + 1, t0 + 89, stats),
                Span("serve.forward", t0 + 20, t0 + 80)]
    return out


def value(found):
    return run.load_reader("ahead_share").value(found)


@pytest.mark.parametrize("aheads, share", [
    ([1, 1, 1, 0], 75.0),
    ([0, 0], 0.0),
    ([1], 100.0),
])
def test_the_share_of_polls_that_dispatched_ahead(aheads, share):
    assert value(polls(aheads)) == pytest.approx(share)


@pytest.mark.parametrize("found", [
    [],
    [s for s in polls([1, 0]) if s.name == "bench.poll"],
    polls([None, None]),
    polls([1, None]),
], ids=["no_spans", "no_serve_batch", "no_statistic", "partly_missing"])
def test_a_program_without_the_statistic_reports_nothing(found):
    assert value(found) is None


def test_an_untraced_run_reports_nothing():
    assert run.load_reader("ahead_share").read(
        types.SimpleNamespace(summary=None)) is None
