"""What every reference's output is compared by, whatever its
equations: the loss of logits and the distance between two sets of
logits. A configuration names its reference (`"reference": "<module>"`
in its file, `llama_ref` when absent): `reference/<module>.py` with
`forward(params, tokens, model) -> logits [t, vocab] float32`."""

from __future__ import annotations

import jax
import jax.numpy as jnp

DEFAULT = "llama_ref"


def load(name=None):
    """The reference module a configuration names, found by file."""
    from benchmark.harness import BenchmarkError, load_module

    module = load_module("reference", name or DEFAULT)
    if not callable(getattr(module, "forward", None)):
        raise BenchmarkError(f"reference {name!r} has no forward()")
    return module


def mean_xent(logits, targets):
    """Mean next-token cross-entropy of logits [t, vocab] float32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def relative_rms_error(got, want) -> float:
    """rms(got - want) / rms(want), on the host in float32 (the two
    may live on different devices)."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(
        np.sqrt(np.mean((got - want) ** 2))
        / max(float(np.sqrt(np.mean(want ** 2))), 1e-30)
    )
