"""`serve_closed`'s traffic, request for request, for a mix whose
configuration the program has only run since the PR that brought it.

The mix's file lists under `requires` the program's files that its
configuration cannot run without. Where one is missing (an older
checkout laid under this benchmark: the driver tries every new cell on
the parent commit first) the run ends HERE, before a cluster starts,
with exit code 1 and the reason. It has to end here: the serve
driver's warm-up sends its requests again for 1,000 s while the
replica refuses to build the model (a `LlamaConfig` key it does not
have), which is a hang where a clean failure is asked for (PERF.md
section 7 says what edit of `drivers/serve.py` would retire this
file). Everything else is `serve_closed`'s: the same warm-up, the same
window, drawn by the same code from the same seed.
"""

from __future__ import annotations

import os

from ..harness import ROOT, BenchmarkError
from . import serve_closed

DRIVER, LOOP = serve_closed.DRIVER, serve_closed.LOOP
generate = serve_closed.generate


def warmup(params: dict, seed: int, vocab_size: int) -> list:
    """`serve_closed.warmup`, once the program's files are there."""
    missing = [
        path for path in params.get("requires", ())
        if not os.path.exists(os.path.join(ROOT, path))
    ]
    if missing:
        raise BenchmarkError(
            "this checkout's program cannot run the mix's configuration: "
            f"it has no {', '.join(missing)}"
        )
    return serve_closed.warmup(params, seed, vocab_size)
