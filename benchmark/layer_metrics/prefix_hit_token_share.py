"""Prompt tokens whose prefill the prefix cache saved by the window's
edge, as a share of the prompt tokens of the requests whose first
token had arrived by then (their prefill was done)."""

LAYER, UNIT, SOURCE = "engine", "%", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    window = run["window_s"]
    prefilled = sum(
        r["n_prompt"] for r in run["requests"]
        if r["token_s"] and r["token_s"][0] <= window
    )
    if not prefilled:
        return None
    saved = (
        engine["after"]["prefix_tokens_saved"]
        - engine["before"]["prefix_tokens_saved"]
    )
    return 100.0 * saved / prefilled
