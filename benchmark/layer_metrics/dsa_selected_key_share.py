"""Of the (query, key) pairs the causal mask and a row's length allow,
the share the main attention attended after the indexer's selection:
100 x `dsa_keys_selected` / `dsa_keys_visible`, the deltas of two
counters in `engine.stats()` that the program itself counts, layer by
layer, in every chunk and every decode step of the window (live rows
only). 100 is no selection (every visible key attended: contexts
inside `index_topk`); a prompt of n tokens under a top-k of k reads
about k (n - k / 2) / (n^2 / 2), a decode step at n keys k / n. A
program whose engine counts no selection gives nothing."""

LAYER, UNIT, SOURCE = "serve forwards", "%", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    before, after = engine["before"], engine["after"]
    if "dsa_keys_visible" not in after or "dsa_keys_selected" not in after:
        return None
    visible = after["dsa_keys_visible"] - before.get("dsa_keys_visible", 0)
    if visible <= 0:
        return None
    selected = after["dsa_keys_selected"] - before.get("dsa_keys_selected", 0)
    return 100.0 * selected / visible
