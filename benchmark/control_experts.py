"""An alias, kept for `tests/test_olmoe_reference_parity.py` (a file
outside the benchmark's paths, which a `benchmark` PR may not edit):
`control.int8_weights` rounds the experts' `[layers, experts, in, out]`
leaves itself since PR 36. Delete this file with that import.

    python3 benchmark/control.py --config <name> [--seeds 1,2,3] [--rehearse]
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.control import int8_weights, main  # noqa: E402,F401

if __name__ == "__main__":
    sys.exit(main())
