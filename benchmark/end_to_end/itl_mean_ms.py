"""Mean gap between streamed tokens that ended in the window, all
requests pooled: the pace a reader sees. It moves with every
interrupted gap, where a percentile sits on a plateau until the share
of interrupted gaps crosses it."""

from benchmark.stats import pooled_gaps_ms


def reduce(run: dict):
    if run.get("requests") is None:
        return None
    gaps = pooled_gaps_ms(run["requests"], run["window_s"])
    return sum(gaps) / len(gaps) if gaps else None
