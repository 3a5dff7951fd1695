"""Keys the decode steps of the window attended over, for every key
that was live: the deltas of two counters in `engine.stats()`,
`kv_keys_read` (all rows, as far as the step's program walks: whole
tiles to the longest alive row) over `kv_keys_live` (the sum of alive
rows' `valid_len`). 1 is a program that reads each live key once;
what is above it is read past a row's end or for a dead slot. A
program whose engine does not count keys gives nothing."""

LAYER, UNIT, SOURCE = "serve forwards", "x", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    before, after = engine["before"], engine["after"]
    if "kv_keys_read" not in after or "kv_keys_live" not in after:
        return None
    live = after["kv_keys_live"] - before.get("kv_keys_live", 0)
    if live <= 0:
        return None
    return (after["kv_keys_read"] - before.get("kv_keys_read", 0)) / live
