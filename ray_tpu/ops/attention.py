"""Attention ops: reference MHA and a Pallas TPU flash-attention kernel.

The reference framework has no fused attention of its own (it defers to
torch); for the TPU build this kernel is the MFU-critical op
(SURVEY.md §7 hard part 4). Design follows the standard TPU flash
pattern: sequential grid over KV blocks with online-softmax state in
VMEM scratch, f32 accumulation, causal block skipping, and a custom
VJP whose backward is ONE fused Pallas kernel computing dq, dk and dv
from a single s/p evaluation per tile (dq accumulates through an
aliased HBM buffer; dk/dv in VMEM scratch).

Layout: [batch, heads, seq, head_dim] with head_dim padded to 128
(MXU lane width). GQA is handled above this op by repeating KV heads.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from ..parallel.sharding import ACT_RULES, checked_shard_map, spec_for

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _interpret() -> bool:
    # The Pallas interpreter is how CPU tests validate the kernels
    # numerically. It is chosen from the platform and is unreachable
    # on a TPU: there Mosaic compiles the kernel or the call raises.
    return jax.default_backend() == "cpu"


def _out_struct(shape, dtype, like) -> jax.ShapeDtypeStruct:
    """Kernel output type that varies over the mesh axes `like` does:
    inside a `shard_map` (flash_attention_sharded) every output is
    per-shard like its inputs, and the replication check wants that
    said; outside one the set is empty."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _mask_logits(s, qi, ki, block_q, block_k, causal, kv_len, window=0):
    """Mask out-of-range KV columns (sequence padded to block
    multiples) and, when causal, future positions; with a window the
    keys that lie `window` or more before the query."""
    rows = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    ) + qi * block_q
    cols = jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    ) + ki * block_k
    valid = cols < kv_len
    if causal:
        valid = jnp.logical_and(valid, rows >= cols)
    if window:
        valid = jnp.logical_and(valid, rows - cols < window)
    return jnp.where(valid, s, DEFAULT_MASK_VALUE)


def _bias_fast_path(causal, block_q, block_k, kv_len, q_len) -> bool:
    """True when diagonal-block masking can use ONE precomputed
    additive bias tile held in VMEM scratch for the kernel's whole
    lifetime. Requires square blocks (every run&masked tile is then an
    exact diagonal with identical relative pattern: qi*bq == ki*bk ⇒
    local rows >= local cols) and no KV/Q padding. The per-tile iota/
    compare/select masking otherwise costs ~6 VPU passes over
    [block_q, block_k] — on a kernel whose MXU work is only two
    d=128-deep matmuls per tile, the VPU, not the MXU, is the
    bottleneck, and one f32 add against a resident tile is the
    cheapest mask that exists."""
    return (
        causal
        and block_q == block_k
        and kv_len % block_k == 0
        and q_len % block_q == 0
    )


def _init_bias_tile(bias_ref, first_step) -> None:
    """Fill the additive causal-mask tile (0 below/on the diagonal,
    -1e38 above) once, at the first grid step; scratch persists across
    the sequential TPU grid so every later diagonal tile reuses it."""

    @pl.when(first_step)
    def _():
        rows = jax.lax.broadcasted_iota(jnp.int32, bias_ref.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, bias_ref.shape, 1)
        bias_ref[:] = jnp.where(
            rows >= cols, 0.0, DEFAULT_MASK_VALUE
        ).astype(bias_ref.dtype)


def _block_runs(qi, ki, block_q, block_k, causal, window=0):
    """Traced predicate (True where nothing is ever skipped): does
    the (qi, ki) tile hold a pair the mask lets through? Causal: its
    last query row is not before its first key; with a window also
    its first query row sees its last key."""
    run = True
    if causal:
        run = qi * block_q + block_q - 1 >= ki * block_k
    if window:
        run &= qi * block_q - (ki * block_k + block_k - 1) < window
    return run


def _first_key_block(qi, block_q, block_k, window):
    """The first key block query block `qi` sees under `window`:
    floor((qi * block_q - window + 1) / block_k), not under 0."""
    return jnp.maximum(qi * block_q - window + 1, 0) // block_k


def _block_needs_mask(qi, ki, block_q, block_k, causal, kv_len, window=0):
    """Traced predicate: does this (qi, ki) tile need logit masking?
    Returns None when masking is statically never needed, so callers
    can skip the branch entirely. Interior tiles (strictly below the
    causal diagonal, no KV padding) take the fast path — the
    iota/compare/select VPU work is a measurable cost at small
    head_dim where the VPU, not the MXU, limits the kernel."""
    may_pad = kv_len % block_k != 0  # static
    if causal:
        on_diag = qi * block_q < ki * block_k + block_k - 1
        if window:
            # the window's edge cuts it: its last query row does not
            # see its first key
            on_diag |= qi * block_q + block_q - 1 - ki * block_k >= window
        if may_pad:
            return on_diag | (ki * block_k + block_k > kv_len)
        return on_diag
    if may_pad:
        return ki * block_k + block_k > kv_len
    return None


def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    window: int = 0,
) -> jax.Array:
    """Readable O(T^2)-memory attention; the numerical ground truth
    for the kernels and the CPU-test fallback. `window` (causal
    only): a query sees the last `window` keys up to itself, its own
    position counted (0: every key up to itself)."""
    *_, t_q, d = q.shape
    t_k = k.shape[-2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        mask = jnp.tril(jnp.ones((t_q, t_k), dtype=bool), k=t_k - t_q)
        if window:
            mask &= ~jnp.tril(
                jnp.ones((t_q, t_k), dtype=bool), k=t_k - t_q - window
            )
        logits = jnp.where(mask, logits, DEFAULT_MASK_VALUE)
    elif window:
        raise ValueError("a window is a causal mask's")
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", weights.astype(v.dtype), v
    ).astype(q.dtype)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(
    q_ref, k_ref, v_ref, out_ref, lse_ref,
    acc_ref, m_ref, l_ref, bias_ref,
    *, causal: bool, block_q: int, block_k: int,
    kv_len: int, fast_mask: bool, window: int = 0,
):
    """Online-softmax flash forward in the log2 domain.

    q arrives PRE-SCALED by scale*log2(e) (see _flash_forward), so the
    raw QK^T dot already holds log2-domain logits: no per-tile scale
    multiply, and exp() becomes the cheaper exp2(). The VPU — not the
    MXU — limits this kernel at head_dim 128 (two d=128 matmuls per
    [bq, bk] tile vs ~4 elementwise passes over it), so every saved
    full-tile pass is ~10% of kernel time. lse is emitted in the SAME
    log2 domain; the backward kernels consume it symmetrically."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    if fast_mask:
        _init_bias_tile(
            bias_ref,
            (pl.program_id(0) == 0) & (qi == 0) & (ki == 0),
        )

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Causal: skip fully-masked KV blocks (q rows all before kv cols,
    # or with a window all `window` or more behind them).
    run = _block_runs(qi, ki, block_q, block_k, causal, window)

    def _update(s, v):
        m_prev = m_ref[:, :1]  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp2(s - m_new)  # [bq, bk] f32
        alpha = jnp.exp2(m_prev - m_new)  # [bq, 1]
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(run)
    def _compute():
        # MXU dots stay in the input dtype (bf16) with f32 accumulation
        # via preferred_element_type — upcasting operands to f32 first
        # would run the matmuls at a fraction of the bf16 MXU rate.
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk] f32, log2-domain logits

        needs_mask = _block_needs_mask(
            qi, ki, block_q, block_k, causal, kv_len, window
        )
        if needs_mask is None:
            _update(s, v)
        elif fast_mask:
            # Square blocks: the only run&masked tiles are exact
            # diagonals — one resident additive tile masks them all.
            @pl.when(needs_mask)
            def _masked():
                _update(s + bias_ref[:], v)

            @pl.when(jnp.logical_not(needs_mask))
            def _interior():
                _update(s, v)
        else:
            @pl.when(needs_mask)
            def _masked():
                _update(
                    _mask_logits(
                        s, qi, ki, block_q, block_k, causal, kv_len,
                        window,
                    ),
                    v,
                )

            @pl.when(jnp.logical_not(needs_mask))
            def _interior():
                _update(s, v)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = (acc_ref[:] / l_safe).astype(out_ref.dtype)
        # lse rides in an 8-sublane layout (TPU block shapes need the
        # second-to-last dim divisible by 8). Log2 domain, like m.
        row = m_ref[:, 0] + jnp.log2(l_safe[:, 0])  # [bq]
        lse_ref[0] = jnp.broadcast_to(row[None, :], lse_ref.shape[1:])


#: Pre-scaling constant: folding softmax scale AND log2(e) into q turns
#: the per-tile `s * scale` pass + natural exp into a bare dot + exp2.
_LOG2E = math.log2(math.e)


def _flash_forward(
    q, k, v, scale, causal, block_q, block_k, kv_len, window=0
):
    bh, t, d = q.shape
    tk = k.shape[1]
    nq = pl.cdiv(t, block_q)
    nk = pl.cdiv(tk, block_k)
    grid = (bh, nq, nk)
    # (the one resident bias tile is the diagonal's: the tile a
    # window's edge cuts has another pattern and takes the iota mask)
    fast_mask = not window and _bias_fast_path(
        causal, block_q, block_k, kv_len, t
    )
    kernel = functools.partial(
        _fwd_kernel,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        kv_len=kv_len,
        fast_mask=fast_mask,
        window=window,
    )
    kv_index = lambda b, i, j: (b, j, 0)  # noqa: E731
    if window:
        # A step the kernel skips names the nearest key block it
        # runs, which the pipeline then has already: no block outside
        # a query block's window is copied in.
        def kv_index(b, i, j):
            first = _first_key_block(i, block_q, block_k, window)
            last = (i * block_q + block_q - 1) // block_k
            return (b, jnp.clip(j, first, last), 0)

    # XLA fuses this multiply into q's producer; inside the kernel it
    # would cost a pass per (qi, ki) tile instead of one per qi block.
    # f32 multiply then cast: the effective logit scale stays exact
    # (only the usual bf16 storage rounding), where a bf16*bf16
    # multiply would perturb the softmax temperature itself.
    q2 = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            _out_struct((bh, t, d), q.dtype, q),
            _out_struct((bh, 8, t), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            # bf16: halves the tile's scoped-VMEM footprint — the f32
            # version pushed the 1024x1024 fwd config 292K past the
            # 16M scoped limit. 0 and -1e38 are both exact in bf16.
            pltpu.VMEM(
                (block_q, block_k) if fast_mask else (8, 128),
                jnp.bfloat16,
            ),
        ],
        interpret=_interpret(),
        # The kernel's name in the compiled program and in a device
        # trace (`%flash_fwd.N`): what kernel time is summed by.
        name="flash_fwd",
    )(q2, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_in_ref,
    dq_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref, bias_ref, dq_all_ref,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    kv_len: int, q_len: int, fast_mask: bool, interp: bool,
    window: int = 0,
):
    """Single-pass backward: dq, dk, dv from ONE s/p computation per
    tile. Split dq + dkv kernels would each recompute s = q2 @ k^T and
    p = exp2(s - lse) — 2 of 7 MXU passes and ~40% of the VPU work
    duplicated. The grid is kv-major (dk/dv accumulate in VMEM
    scratch); dq instead accumulates through an ALIASED HBM buffer
    (dq_in -> dq, f32): its (b, qi) block is revisited
    non-consecutively across ki, so each visit adds this tile's
    contribution. Tiles skipped by the causal test copy the partial
    sum through (the output block is emitted every step regardless).

    Chain-rule factor placement (q2 = scale * log2e * q, lse in the
    log2 domain, so p = exp2(s - lse) equals the natural-domain
    softmax exactly):
      dv = p^T @ do                      — exact as accumulated;
      ds = p * (dp - delta)              — natural-domain ds/scale;
      dq = sum_k (ds @ k) * scale        — scale per [bq, d] tile;
      dk = (sum_q ds^T @ q2) * ln2       — ln2 * log2e == 1 restores
                                           scale * ds^T @ q at the
                                           final store."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    nk = pl.num_programs(1)

    if fast_mask:
        _init_bias_tile(
            bias_ref,
            (pl.program_id(0) == 0) & (ki == 0) & (qi == 0),
        )

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    if interp:
        # Interpreter mode does not preserve written output blocks
        # across non-consecutive revisits (the aliased-HBM dq
        # accumulation below reads back stale input instead), so CPU
        # validation accumulates dq in a full-size scratch — fine at
        # test shapes, unaffordable at real sequence lengths.
        @pl.when((ki == 0) & (qi == 0))
        def _init_dq_all():
            dq_all_ref[:] = jnp.zeros_like(dq_all_ref)

    run = _block_runs(qi, ki, block_q, block_k, causal, window)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][0][:, None]  # log2 domain
        delta = delta_ref[0][0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        def _update(p):
            pb = p.astype(do.dtype)
            dv_acc_ref[:] += jax.lax.dot_general(
                pb, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = (p * (dp - delta)).astype(q.dtype)  # [bq, bk]
            dk_acc_ref[:] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # `scale` applied per TILE on the small [bq, d] result
            # (ds itself omits it — see kernel docstring), so the
            # running dq sum is always final-scaled: no last-tile
            # bookkeeping, and the TPU and interpreter accumulation
            # schemes stay numerically identical.
            dq_tile = jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if interp:
                sl = pl.dslice(qi * block_q, block_q)
                dq_all_ref[sl, :] += dq_tile
                dq_ref[0] = dq_all_ref[sl, :]
            else:
                prev = jnp.where(ki == 0, 0.0, dq_in_ref[0])
                dq_ref[0] = prev + dq_tile

        def _row_masked(p):
            # Padded q rows (beyond q_len) must not contribute.
            row_ids = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            ) + qi * block_q
            return jnp.where(row_ids < q_len, p, 0.0)

        needs_mask = _block_needs_mask(
            qi, ki, block_q, block_k, causal, kv_len, window
        )
        q_may_pad = q_len % block_q != 0  # static
        if q_may_pad:
            row_mask = qi == nq - 1
            needs_mask = (
                row_mask if needs_mask is None else needs_mask | row_mask
            )
        if needs_mask is None:
            _update(jnp.exp2(s - lse))
        elif fast_mask:
            @pl.when(needs_mask)
            def _masked():
                _update(jnp.exp2(s + bias_ref[:] - lse))

            @pl.when(jnp.logical_not(needs_mask))
            def _interior():
                _update(jnp.exp2(s - lse))
        else:
            @pl.when(needs_mask)
            def _masked():
                p = jnp.exp2(_mask_logits(
                    s, qi, ki, block_q, block_k, causal, kv_len, window
                ) - lse)
                _update(_row_masked(p) if q_may_pad else p)

            @pl.when(jnp.logical_not(needs_mask))
            def _interior():
                _update(jnp.exp2(s - lse))

    @pl.when(jnp.logical_not(run))
    def _passthrough():
        # Skipped causal tiles still emit the dq block: carry the
        # partial (already per-tile-scaled) sum forward unchanged.
        if interp:
            sl = pl.dslice(qi * block_q, block_q)
            dq_ref[0] = dq_all_ref[sl, :]
        else:
            dq_ref[0] = dq_in_ref[0]

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = (dk_acc_ref[:] * math.log(2.0)).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _flash_backward_fused(
    q, k, v, out, lse, do, scale, causal, block_q, block_k, kv_len, q_len,
    window=0,
):
    bh, t, d = q.shape
    tk = k.shape[1]
    # f32 intermediates (s, p, dp, ds) plus three accumulators cap the
    # square tile at 512 under the 16 MiB scoped-VMEM budget. Square,
    # so the diagonal-bias fast path applies (_bias_fast_path).
    block_q = min(block_q, 512)
    block_k = min(block_k, 512)
    nq = pl.cdiv(t, block_q)
    nk = pl.cdiv(tk, block_k)
    fast_mask = not window and _bias_fast_path(
        causal, block_q, block_k, kv_len, q_len
    )
    q2 = (q.astype(jnp.float32) * (scale * _LOG2E)).astype(q.dtype)
    delta = jnp.sum(
        out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1
    )  # [bh, t]
    delta = jnp.broadcast_to(delta[:, None, :], (bh, 8, t))
    bias_scratch = pltpu.VMEM(
        (block_q, block_k) if fast_mask else (8, 128), jnp.bfloat16
    )
    # dq accumulator rides in HBM through an aliased input/output pair
    # (its blocks are revisited across ki). Never read at ki == 0;
    # jnp.zeros still materializes a fill (JAX has no uninitialized
    # arrays — ~64 MB/step at bench shapes, ~0.5% of step time), which
    # the alias donates back to the output. Alias-revisit coherency
    # (including the consecutive-revisit nq==1 case) is validated on
    # hardware by the cross-attention grad shapes in the verify
    # recipe — interpret mode cannot model it (see _bwd_fused_kernel).
    dq_seed = jnp.zeros_like(q, jnp.float32)

    interp = _interpret()
    q_index = lambda b, j, i: (b, i, 0)  # noqa: E731
    row_index = lambda b, j, i: (b, 0, i)  # noqa: E731
    if window:
        # As the forward's keys: a step the kernel skips names the
        # nearest query block it runs, so q, do, lse and delta of a
        # block outside a key block's reach are not copied in. (dq's
        # blocks stay where they are: a skipped step carries its
        # partial sum through.)
        def _near(j, i):
            first = (j * block_k) // block_q
            last = (j * block_k + block_k - 1 + window - 1) // block_q
            return jnp.clip(i, first, jnp.minimum(last, nq - 1))

        q_index = lambda b, j, i: (b, _near(j, i), 0)  # noqa: E731
        row_index = lambda b, j, i: (b, 0, _near(j, i))  # noqa: E731
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel,
            scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
            kv_len=kv_len, q_len=q_len, fast_mask=fast_mask,
            interp=interp, window=window,
        ),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, 8, block_q), row_index),
            pl.BlockSpec((1, 8, block_q), row_index),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _out_struct((bh, t, d), jnp.float32, q),
            _out_struct((bh, tk, d), k.dtype, q),
            _out_struct((bh, tk, d), v.dtype, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            bias_scratch,
            # Full-size dq scratch only for interpreter-mode CPU
            # validation (see _bwd_fused_kernel); token-size on TPU.
            pltpu.VMEM(
                (nq * block_q, d) if interp else (8, 128), jnp.float32
            ),
        ],
        input_output_aliases={6: 0},
        interpret=interp,
        name="flash_bwd",
    )(q2, k, v, do, lse, delta, dq_seed)
    return dq.astype(q.dtype), dk, dv


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9)
)
def _flash_attention_bhsd(
    q, k, v, scale, causal, block_q, block_k, kv_len, q_len, window=0
):
    out, _ = _flash_forward(
        q, k, v, scale, causal, block_q, block_k, kv_len, window
    )
    return out


def _flash_fwd_rule(
    q, k, v, scale, causal, block_q, block_k, kv_len, q_len, window
):
    out, lse = _flash_forward(
        q, k, v, scale, causal, block_q, block_k, kv_len, window
    )
    # Residuals carry checkpoint names so a remat policy that saves
    # them (models.llama remat_policy="dots_flash") turns the backward
    # recompute of this kernel into a table lookup: without the names,
    # jax.checkpoint re-RUNS the whole forward flash kernel inside the
    # backward pass just to rebuild (out, lse) — measured at ~15% of
    # the 410M bench step (2.7ms/layer fwd kernel x 24 layers).
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(
    scale, causal, block_q, block_k, kv_len, q_len, window, residuals, do
):
    q, k, v, out, lse = residuals
    dq, dk, dv = _flash_backward_fused(
        q, k, v, out, lse, do, scale, causal, block_q, block_k,
        kv_len, q_len, window,
    )
    return dq, dk, dv


_flash_attention_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    force_pallas: bool = False,
    window: int = 0,
) -> jax.Array:
    """Fused attention. On a TPU this is always the Pallas kernel,
    compiled by Mosaic: a shape the compiler refuses raises, it never
    degrades to the reference. Off-TPU the default is `mha_reference`
    (the interpreter is a test vehicle, far too slow to stand in for
    a backend); `force_pallas=True` runs the kernel there interpreted,
    which is how CPU tests validate it.

    q/k/v: [batch, heads, seq, head_dim]. head_dim should be a
    multiple of 128 for MXU efficiency (callers pad).

    `window` (causal self-attention only): a query sees the last
    `window` keys up to itself, its own position counted. The forward
    and the fused backward skip the key blocks that lie wholly before
    a query block's window, as they skip those behind the diagonal,
    and mask the one the window's edge cuts. A static argument: 0, or
    a window the whole sequence fits, is the plain causal kernel.
    """
    b, h, t, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    tk = k.shape[2]
    if window and not (causal and t == tk):
        raise ValueError(
            "a window is causal self-attention's: "
            f"causal={causal}, {t} queries, {tk} keys"
        )
    if window >= tk:
        window = 0
    if jax.default_backend() != "tpu" and not force_pallas:
        return mha_reference(
            q, k, v, causal=causal, scale=scale, window=window
        )
    block_q = min(block_q, t)
    block_k = min(block_k, tk)
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, tk, d)
    vf = v.reshape(b * h, tk, d)
    # Pad sequences to block multiples with defined zeros; kernels mask
    # columns >= tk (and padded rows in the dk/dv pass), and the q
    # padding is sliced off the output.
    t_pad = -t % block_q
    tk_pad = -tk % block_k
    if t_pad:
        qf = jnp.pad(qf, ((0, 0), (0, t_pad), (0, 0)))
    if tk_pad:
        kf = jnp.pad(kf, ((0, 0), (0, tk_pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, tk_pad), (0, 0)))
    out = _flash_attention_bhsd(
        qf, kf, vf, scale, causal, block_q, block_k, tk, t, window
    )
    return out[:, :t, :].reshape(b, h, t, d)


def flash_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    **kwargs,
) -> jax.Array:
    """`flash_attention` inside a GSPMD `jax.jit` over `mesh`. XLA
    cannot partition a Mosaic custom call, and JAX refuses to lower
    one from a multi-device jit ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map" —
    measured on 4 chips, PR 21). So the kernel runs per shard: batch
    over the data axes and heads over `tp`, as ACT_RULES lays
    activations out; sequence and head_dim stay whole (a softmax row
    needs all of its keys)."""
    spec = spec_for(("batch", "heads", None, None), ACT_RULES)
    return checked_shard_map(
        functools.partial(flash_attention, **kwargs),
        mesh,
        (spec, spec, spec),
        spec,
    )(q, k, v)


def repeat_kv(k: jax.Array, num_rep: int) -> jax.Array:
    """Expand KV heads for grouped-query attention: [b, kvh, t, d] →
    [b, kvh*num_rep, t, d]."""
    if num_rep == 1:
        return k
    b, kvh, t, d = k.shape
    return jnp.repeat(k, num_rep, axis=1)
