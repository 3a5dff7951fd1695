"""Of the keys the window layers would have walked over every live
row's whole length, the share they did walk: 100 x `swa_keys_read` /
`swa_keys_unwindowed`, the deltas of two counters in `engine.stats()`
that the engine counts by the rule the programs' trip counts come from
(`generate.paged_tiles_read` over a window layer's view of a row, and
over the whole row as a full layer walks it), in every chunk and every
decode step of the window, live rows only. A step at a context of n
keys reads about (window + a block) / n of them; 100 means the window
is not used. A program whose engine has no window layers gives
nothing."""

LAYER, UNIT, SOURCE = "serve forwards", "%", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    before, after = engine["before"], engine["after"]
    if "swa_keys_read" not in after or "swa_keys_unwindowed" not in after:
        return None
    whole = after["swa_keys_unwindowed"] - before.get("swa_keys_unwindowed", 0)
    if whole <= 0:
        return None
    return 100.0 * (
        after["swa_keys_read"] - before.get("swa_keys_read", 0)
    ) / whole
