"""Absorbed-MLA decode attention as a flash-decoding Pallas TPU kernel.

One decode step: each slot's absorbed query row ``q = [q_lat; q_rope; 0]``
(H, W) attends the slot's cached rows ``[c_kv; k_rope; 0]`` before its
position, and the new token's own row.  The kernel reads only those live
rows, each once, straight out of the stacked cache ``(R, B, S, W)`` left in
HBM: the layer ``r`` and the rows are picked by the DMA's offsets, so no
layer is sliced out of the cache and no dead row is fetched.

TPU adaptation: the grid runs over groups of slots, one step a group, each
with its queries and outputs as VMEM blocks.  Inside, a loop over the
group's slots, and over each slot's live blocks of ``bk`` rows, keeps an
online softmax (running max, sum and accumulator in f32), seeded with the
token's own score and row.  The blocks are copied HBM -> VMEM by hand,
``BUFFERS`` deep: a cursor walks the blocks to fetch in the order they are
used (past a slot's last block to the next busy slot's first, past a
group's end into the next group's), so one unbroken stream of copies runs
through the step.  Scores and ``p @ kv`` come from the one VMEM copy of
each block, ``p`` in the cache's dtype with f32 accumulation.  Positions
are runtime scalars (scalar prefetch), so one compiled kernel serves every
step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_ROWS = 512          # rows a copy brings in, at most
GROUP_ROWS = 512          # query rows (slots x heads) a grid step holds
BUFFERS = 3               # blocks in VMEM: the one in use, the rest in flight


def block_rows(S: int) -> int:
    """Rows of one copy for a cache of S positions: the largest power of
    two up to ``BLOCK_ROWS`` that divides S."""
    bk = BLOCK_ROWS
    while S % bk:
        bk //= 2
    return bk


def _group(B: int, H: int) -> int:
    """Slots a grid step takes: the largest divisor of B with at most
    ``GROUP_ROWS`` query rows."""
    G = max(1, min(B, GROUP_ROWS // H))
    while B % G:
        G -= 1
    return G


def _first_block(p, bk: int, window: Optional[int]):
    """The first block holding a row a token at ``p`` attends."""
    if window is None:
        return 0
    return jnp.maximum(p - window + 1, 0) // bk


def rows_read(pos, S: int, window: Optional[int] = None) -> int:
    """Cache rows the kernel fetches for slots at positions ``pos`` (host
    integers): each slot's live rows, rounded out to whole blocks."""
    bk = block_rows(S)
    p = np.asarray(pos, np.int64)
    lo = 0 if window is None else np.maximum(p - window + 1, 0) // bk
    return int(np.sum(-(-p // bk) - lo) * bk)


def _kernel(pos_ref, layer_ref, q_ref, *refs, scale: float,
            window: Optional[int], bk: int, G: int, B: int, C: int,
            split: bool):
    # cursor: [0], [1] the slot and block to fetch next, [2] blocks used
    if split:
        kv_hbm, o_ref, lse_ref, buf, sem, cursor = refs
    else:
        own_ref, kv_hbm, o_ref, buf, sem, cursor = refs
    g = pl.program_id(0)
    nblk = kv_hbm.shape[2] // bk
    layer = layer_ref[0]
    nbuf = buf.shape[0]
    dt = jnp.promote_types(q_ref.dtype, buf.dtype)

    def span(b):
        """Slot b's blocks to read, [lo, hi), within the cache's."""
        p = pos_ref[b]
        hi = jnp.clip((p + bk - 1) // bk, 0, nblk)
        return jnp.clip(_first_block(p, bk, window), 0, hi), hi

    def busy_from(b):
        """The first slot from b on with a block to read, or B."""
        def idle(c):
            lo, hi = span(jnp.minimum(c, B - 1))
            return (c < B) & (lo >= hi)
        return jax.lax.while_loop(idle, lambda c: c + 1, b)

    def copy(b, j, k):
        return pltpu.make_async_copy(
            kv_hbm.at[layer, b, pl.ds(pl.multiple_of(j * bk, bk), bk)],
            buf.at[k], sem.at[k])

    def aim(b):
        """Point the fetch cursor at slot b's first block (b = B: done)."""
        cursor[0] = b
        cursor[1] = span(jnp.minimum(b, B - 1))[0]

    def fetch(t):
        """Start the copy of the cursor's block into buffer t's, and move
        the cursor to the block after it, in the next busy slot at a
        slot's end."""
        b, j = cursor[0], cursor[1]

        @pl.when(b < B)
        def _():
            copy(b, j, t % nbuf).start()
            more = j + 1 < span(b)[1]

            @pl.when(more)
            def _():
                cursor[1] = j + 1

            @pl.when(~more)
            def _():
                aim(busy_from(b + 1))

    @pl.when(g == 0)
    def _start():
        cursor[2] = 0
        aim(busy_from(0))
        for t in range(nbuf - 1):
            fetch(t)

    def slot(i, _):
        b = g * G + i
        p = pos_ref[b]
        q = q_ref[i].astype(dt)                                    # (H, W)
        if split:
            m = jnp.full((q.shape[0], 1), NEG_INF, jnp.float32)    # (H, 1)
            l = jnp.zeros_like(m)
            acc = jnp.zeros(q.shape, jnp.float32)
        else:
            # the token's own row seeds the running softmax
            own = own_ref[i]                                       # (1, W)
            m = jnp.sum(q.astype(jnp.float32) * own.astype(jnp.float32),
                        axis=1, keepdims=True) * scale
            l = jnp.ones_like(m)
            acc = jnp.broadcast_to(own.astype(jnp.float32), q.shape)

        def block(j, carry):
            m, l, acc = carry
            t = cursor[2]
            fetch(t + nbuf - 1)             # into the buffer freed last block
            copy(b, j, t % nbuf).wait()
            cursor[2] = t + 1
            kv = buf[t % nbuf]                                     # (bk, W)
            s = jax.lax.dot_general(q, kv.astype(dt),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            valid = kpos < p
            if window is not None:
                valid &= kpos > p - window
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m - m_new)
            e = jnp.exp(s - m_new)
            if split:       # m_new is NEG_INF until a row is valid
                e = jnp.where(valid, e, 0.0)
            l = corr * l + jnp.sum(e, axis=1, keepdims=True)
            acc = corr * acc + jax.lax.dot_general(
                e.astype(kv.dtype), kv, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        lo, hi = span(b)
        m, l, acc = jax.lax.fori_loop(lo, hi, block, (m, l, acc))
        if split:
            o_ref[i] = acc[:, :C] / jnp.maximum(l, 1e-30)
            lse_ref[i] = jnp.where(l > 0, m + jnp.log(l), NEG_INF)
        else:
            o_ref[i] = (acc[:, :C] / l).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, G, slot, 0)


def mla_decode_bhw(q, own, kv, pos, layer, *, scale: float, width: int,
                   window: Optional[int] = None, interpret=False):
    """q: (B, H, W) absorbed query rows; own: (B, 1, W) the new tokens'
    rows; kv: (R, B, S, W) the stacked cache, read at ``layer`` (an int32
    array of shape (1,)); pos: (B,) int32, each slot's position (rows
    [0, pos) are cached).  Scores are taken in the wider of q's and the
    cache's dtypes, ``p @ kv`` in the cache's.  Returns the context's first
    ``width`` columns (B, H, width) in q's dtype.

    With ``own=None``, the split form: kv holds one device's rows, pos
    counts from its first row (any integer), and the result is (o, lse),
    both f32: o (B, H, width) the context over the rows read, normalised by
    their sum of weights, and lse (B, H, 1) that sum's log (``NEG_INF``
    where no row was read, o then 0)."""
    B, H, W = q.shape
    bk = block_rows(kv.shape[2])
    G = _group(B, H)
    split = own is None
    rows = lambda w: pl.BlockSpec((G, H, w), lambda g, pos, r: (g, 0, 0))
    in_specs = [rows(W)] + ([] if split else [
        pl.BlockSpec((G, 1, W), lambda g, pos, r: (g, 0, 0))])
    out_shape = [jax.ShapeDtypeStruct((B, H, width), q.dtype)]
    out_specs = [rows(width)]
    if split:
        out_shape = [jax.ShapeDtypeStruct((B, H, width), jnp.float32),
                     jax.ShapeDtypeStruct((B, H, 1), jnp.float32)]
        out_specs = [rows(width), rows(1)]
    kernel = functools.partial(_kernel, scale=scale, window=window, bk=bk,
                               G=G, B=B, C=width, split=split)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B // G,),
            in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((BUFFERS, bk, W), kv.dtype),
                pltpu.SemaphoreType.DMA((BUFFERS,)),
                pltpu.SMEM((3,), jnp.int32),      # the cursor
            ],
        ),
        out_shape=out_shape,
        # copies started in one grid step are waited for in the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode",
    )(pos, layer, q, *([] if split else [own]), kv)
    return tuple(out) if split else out[0]

