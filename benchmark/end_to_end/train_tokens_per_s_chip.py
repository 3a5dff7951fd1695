"""Tokens of whole steps completed in the window, over the time from
the window's start to the last completion, per chip."""

from benchmark.stats import steps_tokens_per_s


def reduce(run: dict):
    rate = steps_tokens_per_s(run)
    return None if rate is None else rate / run["cell"]["chips"]
