"""Share of the window's admit wait spent `behind_prefill`, in per
cent: a slot was free and another prompt was prefilling, so the
engine's rule of ONE prompt prefilling at a time alone held the queue's
head. It is the time a second prompt in a prefill iteration would win
back (ROADMAP Speed 2). Same counter and sum as `queue_no_slot_share`
(its docstring has the order in which a cause is decided)."""

from benchmark.harness import load_module

LAYER, UNIT, SOURCE = "engine", "%", "program_span"

CAUSES = ("behind_prefill",)


def reduce(run: dict):
    return load_module("layer_metrics", "queue_no_slot_share").share(
        run, CAUSES
    )
