"""Arithmetic the yardstick shares: percentiles, spread, lateness.

Copied in idea from `servebench.py` (`_percentile`); kept here so no
later PR can change how a tail is computed.
"""

from __future__ import annotations

import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (q in [0, 100]) of `values`;
    None for an empty sample. The same rule as numpy's default."""
    if not values:
        return None
    ordered = sorted(values)
    if len(ordered) == 1:
        return float(ordered[0])
    rank = (len(ordered) - 1) * (q / 100.0)
    low = int(rank)
    high = min(low + 1, len(ordered) - 1)
    frac = rank - low
    return float(ordered[low] + (ordered[high] - ordered[low]) * frac)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile over the median:
    the driver's measure of run-to-run noise. The quartiles are those
    of `statistics.quantiles(values, n=4)`, as the driver takes them
    (numpy's lie closer together and would flatter a set of six)."""
    mid = median(values)
    if mid is None or mid == 0 or len(values) < 2:
        return None
    low, _, high = statistics.quantiles(values, n=4)
    return (high - low) / abs(mid)


def lateness_ms(due_s: Sequence[float], sent_s: Sequence[float]) -> list:
    """How late the generator sent each request, in ms (never < 0:
    a request is not sent before it is due)."""
    return [max(0.0, (s - d) * 1e3) for d, s in zip(due_s, sent_s)]


def token_gaps_ms(token_times_s: Sequence[float]) -> list:
    """Gaps between consecutive streamed tokens of one request."""
    return [
        (b - a) * 1e3 for a, b in zip(token_times_s, token_times_s[1:])
    ]


def served(request: dict) -> bool:
    """Completed, or cut by the client after the window's edge: either
    way what it streamed is the service's work. Anything else failed."""
    return bool(request["ok"] or request.get("cut"))


def in_window(requests: Sequence[dict]) -> list:
    """The window's own requests: those not due in the lead-in, which
    only loads the engine before the window opens."""
    return [r for r in requests if not r.get("lead_in")]


def ttfts_ms(requests: Sequence[dict]) -> list:
    """Due-to-first-token of each served request of the window. One cut
    before its first token counts as the wait it had had by then (a
    lower bound). A failed request is left out: the run reports it as
    failed."""
    return [
        (r.get("first_s", r["done_s"]) - r["due_s"]) * 1e3
        for r in in_window(requests) if served(r)
    ]


def pooled_gaps_ms(
    requests: Sequence[dict], until_s: Optional[float] = None
) -> list:
    """Gaps between streamed tokens, all served requests pooled; with
    `until_s`, only gaps that ended inside the window, after 0 and by
    `until_s`, whoever streamed them (a request of the lead-in streams
    into the window like any other)."""
    out = []
    for r in requests:
        if not served(r):
            continue
        ends = r["token_s"][1:]
        out.extend(
            gap for gap, end in zip(token_gaps_ms(r["token_s"]), ends)
            if until_s is None or 0.0 < end <= until_s
        )
    return out


def timer_mean(timers: Optional[dict], name: str) -> Optional[float]:
    """Mean of one of the program's timers over the window, from its
    [sum, count] before and after."""
    if not timers or name not in timers["after"]:
        return None
    s1, n1 = timers["after"][name]
    s0, n0 = timers["before"].get(name, (0.0, 0.0))
    return (s1 - s0) / (n1 - n0) if n1 > n0 else None


def steps_tokens_per_s(run: dict) -> Optional[float]:
    """Tokens of the whole steps completed in the window over the time
    from its start to the last completion (all chips together)."""
    steps = run.get("steps")
    if not steps:
        return None
    return len(steps) * run["tokens_per_step"] / steps[-1][0]
