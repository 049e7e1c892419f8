"""The knee sweep's keep-up test and the warm-up shapes of a traffic mix."""

import numpy as np
import pytest

from bench import loadgen
from bench.sweep import knee_rate, verdict

RATE, SECONDS = 100.0, 10.0


def requests(latency_ms, refused=0):
    """A window of ``RATE`` one-row requests a second whose i-th request
    took ``latency_ms(i)``; the last ``refused`` never came back."""
    n = int(RATE * SECONDS)
    out = []
    for i in range(n):
        r = loadgen.Request(rows=np.array([i]), sent_s=i / RATE)
        if i < n - refused:
            r.latency_s = latency_ms(i) / 1e3
            r.done_s = r.sent_s + r.latency_s
        else:
            r.error = "QueueFull: refused"
        out.append(r)
    return out


@pytest.mark.parametrize("name,latency_ms,refused,keeps_up", [
    ("steady", lambda i: 10.0 + (i % 7), 0, True),
    ("a refused request", lambda i: 10.0 + (i % 7), 1, False),
    ("a backlog that grows", lambda i: 10.0 + i, 0, False),
    ("a tail past twice the median", lambda i: 60.0 if i % 10 == 0 else 10.0,
     0, False),
])
def test_a_rate_is_kept_up_with_only_without_backlog_refusals_or_a_long_tail(
        name, latency_ms, refused, keeps_up):
    row = verdict(requests(latency_ms, refused), SECONDS, RATE)
    assert row["keeps_up"] is keeps_up, (name, row)


@pytest.mark.parametrize("loop,rows,max_batch,warm", [
    ("open", 1, 64, [1, 2, 4, 8, 16, 32, 64]),
    ("open", 1, 16, [1, 2, 4, 8, 16]),
    ("closed", 64, 64, [64]),
    ("closed", 64, 32, [32]),
    ("closed", 48, 64, [1, 2, 4, 8, 16, 32, 64]),
])
def test_warm_up_covers_the_batches_the_engine_can_form(loop, rows, max_batch,
                                                         warm):
    t = loadgen.Traffic(name="t", loop=loop, rows=rows, k=10, pool=64,
                        rate=1.0, clients=1)
    assert t.warm_rows(max_batch) == warm


@pytest.mark.parametrize("kept,knee", [
    ({400: True, 600: False, 800: False, 1400: True}, 400),
    ({1500: True, 1750: True, 2000: False}, 1750),
    ({1000: False, 2000: True}, None),
])
def test_the_knee_is_the_top_of_the_rates_kept_up_with_from_the_lowest(
        kept, knee):
    rows = [{"rate": r, "keeps_up": k} for r, k in kept.items()]
    assert knee_rate(rows[::-1]) == knee
