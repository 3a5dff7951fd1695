"""A decode step's attention over each row's own pages, read from the
block pool where they lie: one Pallas TPU kernel a layer.

A step has one query a row and a row's keys lie in pages of the shared
pool (`[layers, n_blocks, kv_heads, block_len, hd]`), named by the
row's block table. In plain XLA the pages are gathered into a copy a
trip of (row, tile) pairs and the pairs' softmax sums merged into their
rows' by a `[pairs, rows]` mask (models/generate.py `_paged_attention`,
which a chunk and a latent pool still take): the copy is written and
read again, and the merge grows with the square of the batch. Here the
pool goes in whole and stays in HBM; the grid walks the ROWS, and a
row's step walks that row's `tiles[row]` tiles of keys in order, a
loop with a traced bound, so the work is the live tiles and a dead row
(no tile) costs one empty grid step and writes zeros. A tile's pages
(whole pages, every kv head of `block_len` keys: contiguous in the
pool) are copied to VMEM by DMAs started one tile AHEAD, double
buffered, the row's last tile starting the first of the next row that
has any; the row's running softmax (m, l, acc) lives in VMEM in
float32 and its output is written once. A row's result depends on no
other row.

Both products run on the matrix unit with float32 accumulation, one
batched product over the kv heads each: the queries of one kv head
side by side (its `groups` rows, padded to the sublane tile) against a
tile of that head's keys, the weights cast to the page's dtype for the
second. The layer, the tables, and each row's
tiles, query position and length are scalar-prefetched. A window is
one more mask term, a sink one start value of (m, l).

Layout: q [b, heads, 1, hd]; k_pool / v_pool as above (v_pool `None`:
key and value are ONE entry, the values its leading dims, and the
result is as wide as the entry: the caller slices); tables [b, whole
tiles of entries] int32; tiles, pos, length [b] int32 -> [b, heads, 1,
value width] float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: A masked score, and the running max a row starts from: what
#: `_paged_attention` uses, so the two walks agree on a row with a
#: masked key and on one with none.
_MASKED = -1e30


def _interpret() -> bool:
    # (as ops/attention.py: the interpreter on the CPU, Mosaic on a TPU)
    return jax.default_backend() == "cpu"


def _kernel(
    layer_ref, tables_ref, tiles_ref, pos_ref, length_ref,  # prefetched
    q_ref, m0_ref, *rest, pools: int, tile_blocks: int, scale: float,
    window: int, l0: float,
):
    hbm, (out_ref, *rest) = rest[:pools], rest[pools:]
    bufs, (sems, m_ref, l_ref, acc_ref, flight_ref) = rest[:pools], rest[pools:]
    row, rows = pl.program_id(0), pl.num_programs(0)
    width = tables_ref.shape[0] // rows
    kv_heads, tile = bufs[0].shape[1:3]
    block_len = tile // tile_blocks
    n_tiles, layer = tiles_ref[row], layer_ref[0]

    def start(of_row, of_tile, slot):
        """Start the copies of one tile's pages into `slot`."""
        first = of_row * width + of_tile * tile_blocks
        for j in range(tile_blocks):
            page = tables_ref[first + j]
            for pool, (pool_ref, buf) in enumerate(zip(hbm, bufs)):
                pltpu.make_async_copy(
                    pool_ref.at[layer, page],
                    buf.at[slot, :, pl.ds(j * block_len, block_len), :],
                    sems.at[pool, slot],
                ).start()

    def wait(slot):
        # One wait a pool for the whole tile: a wait takes a copy's
        # size off the semaphore and asks for no source, so the slot
        # stands for its pages' copies together (a wait a page cost a
        # step of 36 layers x 2 kv heads 0.6 ms).
        for pool, buf in enumerate(bufs):
            pltpu.make_async_copy(  # rt: noqa[RT008] — a DMA's semaphore
                buf.at[slot], buf.at[slot], sems.at[pool, slot]
            ).wait()

    @pl.when(row == 0)
    def _first_row():
        flight_ref[0] = 0  # the slot the next tile to compute lies in
        flight_ref[1] = 0  # whether its copies were started

    m_ref[...] = m0_ref[...]
    l_ref[...] = jnp.full_like(l_ref, l0)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_tiles > 0)
    def _walk():
        @pl.when(flight_ref[1] == 0)
        def _nobody_started_mine():
            start(row, 0, flight_ref[0])

        # The next row that has a tile (`rows`: none): its first tile
        # is fetched behind this row's last.
        after = jax.lax.while_loop(
            lambda r: (r < rows) & (tiles_ref[jnp.minimum(r, rows - 1)] == 0),
            lambda r: r + 1, row + 1,
        )
        pos, length = pos_ref[row], length_ref[row]

        def one_tile(i, _):
            slot = flight_ref[0]
            last = i + 1 == n_tiles
            next_row = jnp.where(last, after, row)

            @pl.when(next_row < rows)
            def _fetch_ahead():
                start(next_row, jnp.where(last, 0, i + 1), 1 - slot)

            wait(slot)
            groups = q_ref.shape[2]
            k_pos = i * tile + jax.lax.broadcasted_iota(
                jnp.int32, (groups, tile), 1
            )
            seen = (k_pos <= pos) & (k_pos < length)
            if window:
                seen &= k_pos > pos - window
            # Every kv head at once, a batched product each way: head
            # by head the chain product, max, exp, sum, product is all
            # latency (0.3 us a head and tile, whatever the keys: 1.66
            # ms a layer at 64 rows x 8 heads against 0.66 so).
            keys, values = bufs[0][slot], bufs[-1][slot]  # [kvH, tile, hd]
            s = jax.lax.dot_general(
                q_ref[0], keys, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale  # [kvH, groups, tile]
            s = jnp.where(seen, s, _MASKED)
            m_prev, l_prev = m_ref[...][..., :1], l_ref[...][..., :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(
                l_prev * alpha + p.sum(axis=-1, keepdims=True), l_ref.shape
            )
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(values.dtype), values,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            flight_ref[0] = 1 - slot

        jax.lax.fori_loop(0, n_tiles, one_tile, None)
        flight_ref[1] = (after < rows).astype(jnp.int32)

    # A row that walked no tile (a dead row) has l == 0, or a sink's 1
    # over no value: zeros either way.
    l = l_ref[...][..., :1]
    out_ref[0] = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)


def paged_attention(
    q, k_pool, v_pool, layer, tables, tiles, pos, length, *,
    tile_blocks: int, scale: float, window: int = 0, sink=None,
):
    """softmax(q . k * scale) v of each row's one query over the keys
    of its own pages that it may see: key positions up to `pos[row]`,
    under `length[row]` and, with a `window`, above `pos[row] -
    window`. Row r walks its first `tiles[r]` tiles of `tile_blocks`
    table entries (`models/generate.paged_row_tiles`); `sink` [heads]
    is a logit a head in the softmax's sum that carries no value."""
    b, heads, t, hd = q.shape
    kv_heads, block_len = k_pool.shape[2:4]
    if t != 1 or tables.shape[1] % tile_blocks:
        raise ValueError(
            f"{t} queries a row, or a table of {tables.shape[1]} entries "
            f"that is not whole tiles of {tile_blocks}"
        )
    groups = heads // kv_heads
    # (a kv head's queries fill whole sublanes of their dtype)
    sublanes = 32 // q.dtype.itemsize
    padded = -(-groups // sublanes) * sublanes
    rest = ((0, 0), (0, padded - groups))
    qg = jnp.pad(
        q.reshape(b, kv_heads, groups, hd), ((0, 0), *rest, (0, 0))
    )
    m0 = jnp.full((kv_heads, groups), _MASKED, jnp.float32)
    if sink is not None:
        m0 = sink.astype(jnp.float32).reshape(kv_heads, groups)
    m0 = jnp.broadcast_to(
        jnp.pad(m0, rest, constant_values=_MASKED)[..., None],
        (kv_heads, padded, 128),
    )
    pools = [k_pool] if v_pool is None else [k_pool, v_pool]
    tile = tile_blocks * block_len
    dv = pools[-1].shape[-1]

    def a_row(r, *_):
        return (r, 0, 0, 0)

    kernel = functools.partial(
        _kernel, pools=len(pools), tile_blocks=tile_blocks,
        scale=float(scale), window=window,
        l0=0.0 if sink is None else 1.0,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, kv_heads, padded, hd), a_row),
                pl.BlockSpec((kv_heads, padded, 128), lambda *_: (0, 0, 0)),
                *[pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
            ],
            out_specs=pl.BlockSpec((1, kv_heads, padded, dv), a_row),
            scratch_shapes=[
                *[
                    pltpu.VMEM((2, kv_heads, tile, p.shape[-1]), p.dtype)
                    for p in pools
                ],
                pltpu.SemaphoreType.DMA((len(pools), 2)),
                pltpu.VMEM((kv_heads, padded, 128), jnp.float32),
                pltpu.VMEM((kv_heads, padded, 128), jnp.float32),
                pltpu.VMEM((kv_heads, padded, dv), jnp.float32),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv_heads, padded, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # (a row's last tile fetches the next row's first: in order)
            dimension_semantics=("arbitrary",),
        ),
        interpret=_interpret(),
        # Its name in the compiled program and in a device trace
        # (`%paged_attn.N`): what the step's attention is summed by.
        name="paged_attn",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        tables.astype(jnp.int32).reshape(-1),
        *(jnp.asarray(a, jnp.int32) for a in (tiles, pos, length)),
        qg, m0, *pools,
    )
    return out[:, :, :groups].reshape(b, heads, 1, dv)
