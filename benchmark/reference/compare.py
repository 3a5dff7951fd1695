"""What every reference's output is compared by, whatever its
equations: the loss of logits and the distance between two sets of
logits. A configuration names its reference (`"reference": "<module>"`
in its file, `llama_ref` when absent): `reference/<module>.py` with

    forward(params, tokens, model, rows=None) -> logits float32

over `tokens` [t]: every position's, [t, vocab], or with
`rows=(start, stop)` those positions' alone, [stop - start, vocab]: the
layers run over all t positions, the final norm and the head over the
rows that are compared, so that a request as long as `max_len` fits
beside the weights (at 24,576 positions and a vocabulary of 151,936
every position's logits are 14.9 GB). `rows` is part of the contract:
`load` refuses a module whose `forward` lacks it, by name. A module
whose parameter tree is not `weights.shapes`' also defines
`shapes(model)`, the plan `weights.make` draws (`weights.py`)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

DEFAULT = "llama_ref"


def load(name=None, root=None):
    """The reference module a configuration names, found by file
    (under `root`'s benchmark/ where a checkout other than this one is
    looked at)."""
    from benchmark.harness import ROOT, BenchmarkError, load_module

    import inspect

    module = load_module("reference", name or DEFAULT, root or ROOT)
    if not callable(getattr(module, "forward", None)):
        raise BenchmarkError(f"reference {name!r} has no forward()")
    if "rows" not in inspect.signature(module.forward).parameters:
        raise BenchmarkError(
            f"reference {name!r}: forward(params, tokens, model, rows=None) "
            "has no `rows`"
        )
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


def squared_sums(got, want, axis=None) -> tuple:
    """(sum of (got - want)**2, sum of want**2) in float32, computed
    where the arrays are: the parts a relative RMS error over several
    blocks of rows is pooled from (`pooled`). Two floats, or with
    `axis` two lists, one entry per row that is left."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    diff, ref = jax.device_get((
        jnp.sum(jnp.square(got - want), axis=axis),
        jnp.sum(jnp.square(want), axis=axis),
    ))
    return diff.tolist(), ref.tolist()


def pooled(sums) -> float:
    """ONE relative RMS error over everything `squared_sums` was taken
    of: sqrt(sum of squared differences / sum of squared reference).
    Of one pair it is `relative_rms_error`."""
    diff = sum(d for d, _ in sums)
    ref = sum(w for _, w in sums)
    return float((diff / max(ref, 1e-60)) ** 0.5)
