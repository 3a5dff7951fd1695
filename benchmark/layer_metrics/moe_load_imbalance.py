"""How unevenly the router filled the experts in the window's prefill
chunks: per chunk and layer, the fullest expert's tokens over the even
share (the chunk's picks over the number of experts), averaged. From
the deltas of `engine.stats()`: `moe_chunk_max_load` (the fullest
expert's tokens, summed over chunk-layers) over `moe_picks_prefill` /
experts (the even shares, summed likewise). 1 is even routing; the
grouped matmuls wait for the fullest group. A dense engine counts
none of these and gives nothing."""

LAYER, UNIT, SOURCE = "engine", "x", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    experts = run["config"]["model"].get("moe_experts")
    if not engine or not experts:
        return None
    before, after = engine["before"], engine["after"]
    if "moe_chunk_max_load" not in after:
        return None
    picks = after["moe_picks_prefill"] - before.get("moe_picks_prefill", 0)
    if picks <= 0:
        return None
    load = after["moe_chunk_max_load"] - before.get("moe_chunk_max_load", 0)
    return load / (picks / experts)
