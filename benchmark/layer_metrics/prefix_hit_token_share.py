"""Prompt tokens whose prefill the prefix cache saved inside the
window, as a share of the prompt tokens of the requests whose first
token arrived in it (their prefill was done by then)."""

LAYER, UNIT, SOURCE = "engine", "%", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    window = run["window_s"]
    prefilled = sum(
        r["n_prompt"] for r in run["requests"]
        if r["token_s"] and 0.0 < r["token_s"][0] <= window
    )
    if not prefilled:
        return None
    saved = (
        engine["after"]["prefix_tokens_saved"]
        - engine["before"]["prefix_tokens_saved"]
    )
    return 100.0 * saved / prefilled
