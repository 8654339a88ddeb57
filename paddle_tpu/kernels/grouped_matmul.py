"""Grouped matrix products (the held experts' products of an expert layer)
as Pallas TPU kernels.

The rows of the left operand ``lhs [m, k]`` are sorted by group: group g
owns the ``sizes[g]`` rows from ``sum(sizes[:g])`` on, and the rows past
``sum(sizes)`` belong to no group. Three kernels (``KERNELS``):

- ``gmm``: ``out[r] = lhs[r] @ rhs[g(r)]``, rhs ``[G, k, n]`` -> ``[m, n]``;
- ``gmm_t``: the same against each group's transpose, rhs ``[G, n, k]``
  (the left operand's gradient);
- ``tgmm``: ``out[g] = lhs[rows of g]^T @ rhs[rows of g]``, rhs ``[m, n]``
  -> ``[G, k, n]`` (the right operand's gradient).

All three walk the rows in tiles of ``tm``, group by group, each block as
wide as its operand: a tile that a group boundary crosses is visited once
by each group with rows in it, and each visit keeps only its group's rows
(the product of the others' rows is made and dropped). A tile wholly past
``sum(sizes)`` does no MXU work and reads no operand: its steps keep the
blocks of the last tile that did, which the pipeline does not fetch again.
Contract: every row past ``sum(sizes)`` of a ``gmm`` / ``gmm_t`` result is
exactly 0 (whatever the operand holds there, NaN included), so those two
still STORE a tile of zeros there: their cost grows with the rows past
``sum(sizes)`` by those rows' HBM writes (``n`` f32 or bf16 values a row).
``tgmm`` takes no step there and leaves those rows out, masking both
operands' rows wherever a tile is not wholly one group's; an empty group's
result is 0. Products take the left operand's dtype and accumulate in f32.
A right operand of ``gmm`` / ``gmm_t`` of another dtype (an f32 master
weight under AMP) is cast in VMEM once a group, so no cast copy of it
passes through HBM, and ``tgmm`` writes the weight gradient in the
weight's own dtype from its f32 sums.

How a call tiles is a pure function of its shape (``default_tiling``);
``tune.counters()["gmm_blocks"]`` says what every traced launch was built
with. Each launch of one shape is traced once (``_launch``), however many
layers and passes make it, and its walk over the row tiles is lowered once
a shape (``_walk_call``).

``grouped_matmul`` is the differentiable entry point (a ``custom_vjp``: its
gradients are ``gmm_t`` and ``tgmm``). No reference equivalent: the 2018
codebase has no expert layers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..place import on_tpu

LANE = 128
KERNELS = ("gmm", "gmm_t", "tgmm")
ROW_TILES = (256, 128)
# what Mosaic grants one kernel on a v5e unless told otherwise (its scoped
# vmem limit), and the most a launch asks for (half a v5e core's 128 MiB)
VMEM_LIMIT = 16 * 1024 * 1024
VMEM_MOST = 64 * 1024 * 1024

_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def _block_bytes(rows, cols, itemsize):
    """VMEM bytes of a [rows, cols] block: lanes round up to 128, rows to
    the dtype's sublane tile (8 for f32, 16 for bf16)."""
    sub = 8 * 4 // itemsize
    return (-(-rows // sub) * sub) * (-(-cols // LANE) * LANE) * itemsize


def vmem_bytes(kernel, tm, tk, tn, itemsize, out_itemsize, rhs_itemsize=None):
    """What ``kernel`` keeps in VMEM at tiling (tm, tk, tn) for a left
    operand of ``itemsize`` bytes (and a right one of ``rhs_itemsize``,
    the same if None): every operand and result block twice (the pipeline's
    two buffers), the right operand's block cast to the left's dtype where
    the two differ, ``tgmm``'s f32 accumulator, and the f32 tiles live at
    once (the product, and for a tile a group boundary crosses the row masks
    and the masked operands or the kept result)."""
    rhs_itemsize = rhs_itemsize or itemsize
    blk = functools.partial(_block_bytes, itemsize=itemsize)
    f32 = functools.partial(_block_bytes, itemsize=4)
    if kernel in ("gmm", "gmm_t"):
        piped = (blk(tm, tk) + _block_bytes(tk, tn, rhs_itemsize)
                 + _block_bytes(tm, tn, out_itemsize))
        cast = blk(tk, tn) if rhs_itemsize != itemsize else 0
        return 2 * piped + cast + 4 * f32(tm, tn)
    if kernel == "tgmm":
        piped = blk(tm, tk) + blk(tm, tn) + _block_bytes(tk, tn, out_itemsize)
        return 2 * piped + 2 * f32(tk, tn) + 2 * (f32(tm, tk) + f32(tm, tn))
    raise ValueError("no grouped-matmul kernel %r" % (kernel,))


def vmem_limit(kernel, tm, tk, tn, itemsize, out_itemsize, rhs_itemsize=None):
    """The scoped VMEM a launch asks Mosaic for: None (Mosaic's default,
    VMEM_LIMIT) where ``vmem_bytes`` fits under it, else the reckoning
    rounded up to a MiB."""
    need = vmem_bytes(kernel, tm, tk, tn, itemsize, out_itemsize,
                      rhs_itemsize)
    return None if need <= VMEM_LIMIT else -(-need // 2 ** 20) * 2 ** 20


def _lane_splits(width):
    """Block widths narrower than ``width`` that tile it in whole lanes,
    widest first (none where ``width`` is no multiple of 128)."""
    if width % LANE:
        return []
    lanes = width // LANE
    return [LANE * d for d in range(lanes - 1, 0, -1) if lanes % d == 0]


def default_tiling(kernel, m, k, n, itemsize, out_itemsize=4,
                   rhs_itemsize=None):
    """(tm, tk, tn): the row tile of ``kernel`` for a call of ``m`` rows,
    contracted width ``k`` (``tgmm``: the rows) and result width ``n``
    (``tgmm``: the result is [groups, k, n]), and the widths of its blocks.
    A pure function of the call's shape, read off the sweep in PERF.md §6.

    Rows: the widest of 256 / 128 that divides ``m``, for all three kernels
    (128 and 256 timed alike, within 5%, in every cell; 512 was 5-10%
    slower: each tile a group boundary crosses is made once a group in it).
    A call of fewer rows than 128 is one tile; other row counts are padded
    to a multiple of 128 (``_run``). Blocks are as wide as their operands,
    so each group's right operand enters VMEM once and nothing is summed
    across steps but ``tgmm``'s rows, wherever ``vmem_bytes`` of the whole
    widths fits VMEM_MOST (every launch of K, T and P: T's ``tgmm``, the
    largest, asks for ~41 MiB). Where it does not: first a narrower row
    tile at whole widths; then the widest result block, in whole lanes,
    that fits (``gmm`` / ``gmm_t``: the result width ``n``; ``tgmm``: ``n``,
    or where ``n`` is no multiple of 128 the result's rows ``k``), each
    such block a walk of its own over the rows. Raises where nothing
    fits."""
    tm = next((t for t in ROW_TILES if m % t == 0), m if m < LANE else LANE)
    need = functools.partial(vmem_bytes, kernel, itemsize=itemsize,
                             out_itemsize=out_itemsize,
                             rhs_itemsize=rhs_itemsize)
    for rows in [tm] + [t for t in ROW_TILES if t < tm and m % t == 0]:
        if need(rows, k, n) <= VMEM_MOST:
            return rows, k, n
    splits = [(k, tn) for tn in _lane_splits(n)]
    if kernel == "tgmm" and not splits:
        splits = [(tk, n) for tk in _lane_splits(k)]
    for tk, tn in splits:
        if need(tm, tk, tn) <= VMEM_MOST:
            return tm, tk, tn
    raise ValueError(
        "grouped %s of widths %d x %d needs %d B of VMEM, past the %d a "
        "launch may ask for" % (kernel, k, n, need(tm, k, n), VMEM_MOST))


def _walk(sizes, m, tm, visit_empty):
    """The scalars a launch's index maps and body read, (offsets [G + 1],
    group [S], tile_in [S], tile_out [S], n_live [1]), S = m / tm + G - 1
    the most steps a walk can take, and the steps this one takes. Steps
    ``< n_live`` visit the tiles of each group in turn (``visit_empty``: an
    empty group takes one step, to zero its ``tgmm`` result). ``gmm`` /
    ``gmm_t`` (not ``visit_empty``) take one step more for each tile wholly
    past ``sum(sizes)``, which ``tile_out`` walks to store zeros there while
    ``group`` and ``tile_in`` stay at the last live step's, so that no
    operand block changes; ``tgmm`` takes none."""
    # (lax, not jnp: a launch's walk is traced and lowered once a shape,
    # and jnp's wrappers cost a step's set-up several times what these do)
    i32 = jnp.int32
    groups = sizes.shape[0]
    tiles = m // tm
    steps = tiles + groups - 1
    full = lambda v, n: lax.full((n,), v, i32)
    ends = lax.cumsum(sizes)
    first = lax.div(lax.sub(ends, sizes), full(tm, groups))
    span = lax.select(
        lax.gt(sizes, full(0, groups)),
        lax.sub(lax.div(lax.add(ends, full(tm - 1, groups)),
                        full(tm, groups)), first),
        full(int(visit_empty), groups))
    step_end = lax.cumsum(span)
    n_live = lax.index_in_dim(step_end, groups - 1, keepdims=False)
    total = lax.index_in_dim(ends, groups - 1, keepdims=False)
    # step s belongs to the group whose steps end first after it
    by_step = lambda v: lax.broadcast_in_dim(v, (steps, groups), (1,))
    s = lax.iota(i32, steps)
    ended = lax.ge(lax.broadcasted_iota(i32, (steps, groups), 0),
                   by_step(step_end))
    g = lax.min(lax.reduce(lax.convert_element_type(ended, i32), i32(0),
                           lax.add, (1,)), full(groups - 1, steps))
    mine = lax.eq(lax.broadcasted_iota(i32, (steps, groups), 1),
                  lax.broadcast_in_dim(g, (steps, groups), (0,)))
    base = lax.reduce(lax.select(mine, by_step(lax.sub(first, lax.sub(
        step_end, span))), lax.full((steps, groups), 0, i32)), i32(0),
        lax.add, (1,))
    tile = lax.clamp(i32(0), lax.add(base, s), i32(tiles - 1))
    live = lax.lt(s, lax.broadcast(n_live, (steps,)))
    last = lax.max(lax.sub(n_live, i32(1)), i32(0))
    at_last = lambda v: lax.broadcast(
        lax.dynamic_index_in_dim(v, last, keepdims=False), (steps,))
    used = lax.div(lax.add(total, i32(tm - 1)), i32(tm))
    past = lax.min(lax.add(lax.sub(s, lax.broadcast(n_live, (steps,))),
                           lax.broadcast(used, (steps,))), full(tiles - 1,
                                                                steps))
    taken = n_live if visit_empty else lax.add(n_live, lax.sub(i32(tiles),
                                                                 used))
    return ((lax.concatenate([full(0, 1), ends], 0),
             lax.select(live, g, at_last(g)),
             lax.select(live, tile, at_last(tile)),
             lax.select(live, tile, past), lax.broadcast(n_live, (1,))),
            taken)


# the walk as a call of its own, not inlined: a rung's launches (and every
# layer's and pass's call of them) make the same walk of the same sizes, so
# the step lowers it once a shape instead of once a call site
_walk_call = jax.jit(_walk, static_argnums=(1, 2, 3))

# the kernels' scalar and mask logic in lax for the same reason as the walk
_and, _or, _not = lax.bitwise_and, lax.bitwise_or, lax.bitwise_not
_I32 = jnp.int32


def _rows_in(shape, row0, start, end):
    """[rows, cols] mask: the block's rows, from ``row0`` on, that lie in
    [start, end)."""
    rows = lax.add(lax.broadcasted_iota(_I32, shape, 0),
                   lax.broadcast(row0, shape))
    return _and(lax.ge(rows, lax.broadcast(start, shape)),
                lax.lt(rows, lax.broadcast(end, shape)))


def _before(s, ref):
    """``ref`` at the step before ``s`` (at ``s`` itself for the first)."""
    return ref[lax.max(lax.sub(s, _I32(1)), _I32(0))]


def _gmm_kernel(offsets, group, _tile_in, tile_out, n_live, lhs_ref, rhs_ref,
                out_ref, *cast_ref, tm, dims, axis):
    """``gmm`` / ``gmm_t`` (``dims``); ``cast_ref``: where the right
    operand's dtype is not the left's, its block in the left's dtype. The
    walk's step is grid axis ``axis`` (1 where the result's width is cut
    into blocks: axis 0 walks them)."""
    from jax.experimental import pallas as pl
    s = pl.program_id(axis)
    live = lax.lt(s, n_live[0])
    g = group[s]
    start, end = offsets[g], offsets[lax.add(g, _I32(1))]
    row0 = lax.mul(tile_out[s], _I32(tm))
    whole = _and(lax.ge(row0, start), lax.le(lax.add(row0, _I32(tm)), end))
    first = _or(lax.eq(s, _I32(0)), lax.ne(_before(s, tile_out),
                                           tile_out[s]))
    f32 = jnp.float32
    rhs = rhs_ref
    if cast_ref:
        rhs = cast_ref[0]

        # a new right-operand block: each group's first step
        @pl.when(_or(lax.eq(s, _I32(0)), lax.ne(_before(s, group), g)))
        def _cast():
            rhs[...] = lax.convert_element_type(rhs_ref[...], rhs.dtype)

    def product():
        return lax.dot_general(lhs_ref[...], rhs[...], dims,
                               preferred_element_type=f32)

    shape, dtype = out_ref.shape, out_ref.dtype

    def masked(kept):
        out_ref[...] = lax.convert_element_type(lax.select(
            _rows_in(shape, row0, start, end), product(), kept), dtype)

    @pl.when(_and(live, whole))
    def _whole():
        out_ref[...] = lax.convert_element_type(product(), dtype)

    # the first visit to a tile owns what no group's rows set there
    @pl.when(_and(live, _and(_not(whole), first)))
    def _first():
        masked(lax.full(shape, 0.0, f32))

    @pl.when(_and(live, _and(_not(whole), _not(first))))
    def _again():
        masked(lax.convert_element_type(out_ref[...], f32))

    @pl.when(_and(_not(live), first))
    def _past():
        out_ref[...] = lax.full(shape, 0, dtype)


def _tgmm_kernel(offsets, group, tile_in, _tile_out, n_live, lhs_ref, rhs_ref,
                 out_ref, acc_ref, *, tm, steps, axis):
    """``tgmm``: one group's [k, n] result (a block of it), summed in f32
    over the group's row tiles; the walk's step is grid axis ``axis``."""
    from jax.experimental import pallas as pl
    s = pl.program_id(axis)
    n = n_live[0]
    live = lax.lt(s, n)
    g = group[s]
    start, end = offsets[g], offsets[lax.add(g, _I32(1))]
    row0 = lax.mul(tile_in[s], _I32(tm))
    whole = _and(lax.ge(row0, start), lax.le(lax.add(row0, _I32(tm)), end))
    first = _or(lax.eq(s, _I32(0)), lax.ne(_before(s, group), g))
    last = _or(lax.eq(s, lax.sub(n, _I32(1))),
               lax.ne(group[lax.min(lax.add(s, _I32(1)), _I32(steps - 1))],
                      g))
    f32 = jnp.float32

    @pl.when(first)
    def _zero():
        acc_ref[...] = lax.full(acc_ref.shape, 0.0, f32)

    def accumulate(x, y):
        acc_ref[...] = lax.add(acc_ref[...], lax.dot_general(
            x, y, _TN, preferred_element_type=f32))

    @pl.when(_and(live, whole))
    def _whole():
        accumulate(lhs_ref[...], rhs_ref[...])

    @pl.when(_and(_and(live, _not(whole)), lax.gt(end, start)))
    def _boundary():
        def rows_of_g(ref):
            return lax.convert_element_type(lax.select(
                _rows_in(ref.shape, row0, start, end),
                lax.convert_element_type(ref[...], f32),
                lax.full(ref.shape, 0.0, f32)), ref.dtype)
        accumulate(rows_of_g(lhs_ref), rows_of_g(rhs_ref))

    @pl.when(_and(live, last))
    def _store():
        out_ref[...] = lax.convert_element_type(acc_ref[...], out_ref.dtype)


@functools.lru_cache(maxsize=None)
def _launch(kernel, m, k, n, groups, dtype, rhs_dtype, out_dtype, tiling,
            interpret):
    """``kernel``'s launch at these shapes, dtypes and ``tiling`` (tm, tk,
    tn), traced ONCE: ``fn(lhs, rhs, sizes)``. ``gmm``: lhs [m, k], rhs
    [groups, k, n]; ``gmm_t``: rhs [groups, n, k]; both -> [m, n]. ``tgmm``:
    lhs [m, k], rhs [m, n] -> [groups, k, n]. The grid's steps are the
    walk's (``_walk``), counted on the device; where the result's width is
    cut into blocks (tk < k or tn < n) a first grid axis walks the blocks,
    each a whole walk over the rows."""
    import jax.extend
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    tm, tk, tn = tiling
    steps = m // tm + groups - 1
    cut_n = n // tn
    blocks = (k // tk) * cut_n
    itemsize, rhs_itemsize, out_itemsize = (
        jnp.dtype(t).itemsize for t in (dtype, rhs_dtype, out_dtype))
    if blocks == 1:
        at = lambda f: (lambda s, o, g, ti, to, nl: f(s, 0, g, ti, to))
    else:
        at = lambda f: (lambda j, s, o, g, ti, to, nl: f(s, j, g, ti, to))
    axis = int(blocks > 1)
    if kernel == "tgmm":
        body = functools.partial(_tgmm_kernel, tm=tm, steps=steps, axis=axis)
        in_specs = [pl.BlockSpec((tm, tk), at(lambda s, j, g, ti, to:
                                              (ti[s], j // cut_n))),
                    pl.BlockSpec((tm, tn), at(lambda s, j, g, ti, to:
                                              (ti[s], j % cut_n)))]
        out_specs = pl.BlockSpec((None, tk, tn), at(
            lambda s, j, g, ti, to: (g[s], j // cut_n, j % cut_n)))
        out_shape = (groups, k, n)
        scratch = [pltpu.VMEM((tk, tn), jnp.float32)]
        nbytes = (m * k * itemsize * cut_n + m * n * rhs_itemsize
                  * (k // tk) + groups * k * n * out_itemsize)
    else:
        rhs_block = (tn, k) if kernel == "gmm_t" else (k, tn)
        body = functools.partial(_gmm_kernel, tm=tm, axis=axis,
                                 dims=_NT if kernel == "gmm_t" else _NN)
        in_specs = [pl.BlockSpec((tm, k), at(lambda s, j, g, ti, to:
                                             (ti[s], 0))),
                    pl.BlockSpec((None,) + rhs_block, at(
                        lambda s, j, g, ti, to: (g[s], j, 0)
                        if kernel == "gmm_t" else (g[s], 0, j)))]
        out_specs = pl.BlockSpec((tm, tn), at(lambda s, j, g, ti, to:
                                              (to[s], j)))
        out_shape = (m, n)
        scratch = ([pltpu.VMEM(rhs_block, dtype)]
                   if jnp.dtype(rhs_dtype) != jnp.dtype(dtype) else [])
        nbytes = m * k * itemsize * blocks + groups * k * n * rhs_itemsize \
            + m * n * out_itemsize
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * (1 + axis),
        vmem_limit_bytes=vmem_limit(kernel, tm, tk, tn, itemsize,
                                    out_itemsize, rhs_itemsize))

    def fn(lhs, rhs, sizes):
        walk, taken = _walk_call(sizes, m, tm, kernel == "tgmm")
        call = pl.pallas_call(
            body,
            out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(taken,) if blocks == 1 else (blocks, taken),
                in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=scratch),
            compiler_params=params,
            cost_estimate=pl.CostEstimate(flops=2 * m * k * n,
                                          transcendentals=0,
                                          bytes_accessed=nbytes),
            interpret=interpret,
            name="grouped_" + kernel)
        return call(*walk, lhs, rhs)

    rhs_shape = {"gmm": (groups, k, n), "gmm_t": (groups, n, k),
                 "tgmm": (m, n)}[kernel]
    traced = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((m, k), dtype),
                                jax.ShapeDtypeStruct(rhs_shape, rhs_dtype),
                                jax.ShapeDtypeStruct((groups,), jnp.int32))
    return jax.extend.core.jaxpr_as_fun(traced)


def _run(kernel, lhs, rhs, sizes, out_dtype):
    """One launch of ``kernel`` at the tiling ``default_tiling`` gives the
    call's shape, counted (``tune.counters()["gmm_blocks"]``). Rows that
    no tile of the rule divides are padded with zeros (past every group)
    and cut off again."""
    from .. import tune
    m, k = lhs.shape
    groups = sizes.shape[0]
    n = rhs.shape[1] if kernel in ("gmm_t", "tgmm") else rhs.shape[2]
    tiling = default_tiling(kernel, m, k, n, lhs.dtype.itemsize,
                            jnp.dtype(out_dtype).itemsize, rhs.dtype.itemsize)
    tune.count_gmm_blocks(kernel, *tiling, groups)
    pad = -m % tiling[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        if kernel == "tgmm":
            rhs = jnp.pad(rhs, ((0, pad), (0, 0)))
    launch = _launch(kernel, m + pad, k, n, groups, jnp.dtype(lhs.dtype),
                     jnp.dtype(rhs.dtype), jnp.dtype(out_dtype), tiling,
                     not on_tpu())
    out = launch(lhs, rhs, sizes.astype(jnp.int32))[0]
    return out[:m] if pad and kernel != "tgmm" else out


@jax.custom_vjp
def grouped_matmul(lhs, rhs, sizes):
    """``lhs [m, k]`` (rows sorted by group, ``sum(sizes) <= m``) times
    each row's group's ``rhs [G, k, n]`` -> ``[m, n]`` f32; rows past
    ``sum(sizes)`` are exactly 0, and so are those rows of the left
    operand's gradient. The products take ``lhs``'s dtype (bf16 or f32)
    and accumulate in f32; an ``rhs`` of another dtype (an f32 master
    weight under AMP) is cast to it in VMEM. Each gradient takes its
    operand's dtype. Pallas kernels on the TPU, interpret mode elsewhere."""
    return _run("gmm", lhs, rhs, sizes, jnp.float32)


def _grouped_fwd(lhs, rhs, sizes):
    return grouped_matmul(lhs, rhs, sizes), (lhs, rhs, sizes)


def _grouped_bwd(res, ct):
    lhs, rhs, sizes = res
    ct = ct.astype(lhs.dtype)
    # d lhs = ct @ rhs[g]^T a row; d rhs[g] = lhs_g^T @ ct_g
    return (_run("gmm_t", ct, rhs, sizes, lhs.dtype),
            _run("tgmm", lhs, ct, sizes, rhs.dtype), None)


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)
