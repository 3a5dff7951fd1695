"""What the conv layers' states cost beside the attention layers'
pages: 100 x `conv_state_bytes_in_use` / (`conv_state_bytes_in_use` +
`kv_bytes_in_use`), two gauges of `engine.stats()` read at the window's
end. The first is the bytes of the state slots that hold a row's state
or a kept snapshot (`llm/kv_state.py`: one snapshot a whole-chunk
boundary a prefill passed, until it is evicted), the second the bytes
of the pages held by rows or by the prefix cache. A program that keeps
fewer snapshots moves this down, and `conv_hit_kept_share` with it
where the ones it dropped were needed. A program whose engine has
neither gauge, or holds nothing of either, gives nothing."""

LAYER, UNIT, SOURCE = "engine", "%", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    if not engine:
        return None
    after = engine["after"]
    if "conv_state_bytes_in_use" not in after or "kv_bytes_in_use" not in after:
        return None
    states, pages = after["conv_state_bytes_in_use"], after["kv_bytes_in_use"]
    if states + pages <= 0:
        return None
    return 100.0 * states / (states + pages)
