"""The share of the experts' weights a decode step had to read: per
step and layer, the experts that got at least one live row's token,
over all experts. From the deltas of `engine.stats()`:
`moe_experts_touched` over experts x `moe_step_layers`. Under even
routing `B` live rows touch 1 - (1 - k/E)^B of them. A dense engine
counts neither and gives nothing."""

LAYER, UNIT, SOURCE = "serve forwards", "%", "program_counter"


def reduce(run: dict):
    engine = run.get("engine")
    experts = run["config"]["model"].get("moe_experts")
    if not engine or not experts:
        return None
    before, after = engine["before"], engine["after"]
    if "moe_experts_touched" not in after:
        return None
    layers = after["moe_step_layers"] - before.get("moe_step_layers", 0)
    if layers <= 0:
        return None
    touched = after["moe_experts_touched"] - before.get(
        "moe_experts_touched", 0
    )
    return 100.0 * touched / (experts * layers)
