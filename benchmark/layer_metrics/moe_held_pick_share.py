"""Of the (token, expert) picks the router made over ALL its outputs,
the share that met an expert held on this chip: 100 x
(`moe_picks_prefill` + `moe_picks_decode`) / `moe_picks_routed`, the
deltas of `engine.stats()` counters the program counts in every expert
layer of every forward (live rows only). A chip that holds 16 of 256
experts reads 6.25 under even routing: the distance from it is how far
this chip's expert load lies from an even sixteenth (the seeded
correction bias and the group limit both move it). An engine that
holds every expert it routes over counts no `moe_picks_routed` and
gives nothing."""

LAYER, UNIT, SOURCE = "engine", "%", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    before, after = engine["before"], engine["after"]
    if "moe_picks_routed" not in after:
        return None

    def delta(key):
        return after.get(key, 0) - before.get(key, 0)

    routed = delta("moe_picks_routed")
    if routed <= 0:
        return None
    return 100.0 * (
        delta("moe_picks_prefill") + delta("moe_picks_decode")
    ) / routed
