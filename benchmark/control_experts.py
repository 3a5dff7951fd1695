"""`control.py` for a configuration with experts: the same control of
`correct` (the plain reference with int8 weights in the program's
place), with the experts' matrices rounded too.

    python3 benchmark/control_experts.py --config <name> [--seeds 1,2,3] [--rehearse]

`control.int8_weights` rounds the leaves stacked `[layers, in, out]`
and leaves every other alone, so it would keep an expert layer's
`[layers, experts, in, out]` leaves exact: 96 % of OLMoE's weights.
Here those go through the same `int8_matrix`, one expert's matrix at a
time (one scale per output channel of each expert), and everything
else through `control.int8_weights` as it is. The router `[layers, d,
E]` is a 3-D leaf and is rounded like any matmul weight: an 8-bit path
that keeps the router exact reads less. The printed rows are
`control.control_errors`' rows.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control  # noqa: E402

_three_dimensional = control.int8_weights


def int8_weights(params: dict) -> dict:
    """`control.int8_weights`, then every `[layers, experts, in, out]`
    leaf under `layers`, an expert's matrix at a time. The old leaves
    are donated, as there."""
    import jax

    experts = jax.jit(
        lambda w: jax.lax.map(
            lambda layer: jax.lax.map(control.int8_matrix, layer), w
        ),
        donate_argnums=0,
    )
    out = _three_dimensional(params)
    out["layers"] = {
        name: experts(w) if w.ndim == 4 else w
        for name, w in out["layers"].items()
    }
    return out


def main() -> int:
    # `control.control_errors` and `control.main` find `int8_weights`
    # in their module: put this one there for this process.
    control.int8_weights = int8_weights
    return control.main()


if __name__ == "__main__":
    sys.exit(main())
