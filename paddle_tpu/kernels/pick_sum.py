"""The sum of each token's held picks, read back from an expert layer's
sorted rows, as a Pallas TPU kernel.

An expert layer sorts its (token, pick) pairs by expert, held experts
first (stable): pair ``(t, j)`` sits at row ``inv[t, j]`` of the sorted
order, and the first ``held = sum(sizes)`` rows are the pairs that fell on
a held expert, ``sizes[g]`` rows of held expert g after those of the
experts before it. Given the sorted rows ``a [rows, d]`` (the weighted
expert results, or the cotangent of the rows the layer handed its experts)

    out[t] = sum over j with inv[t, j] < held of f32(a[inv[t, j]])

(``pick_sum``). A pick past ``held`` adds nothing: whatever a row holds
there, NaN included, never reaches a result.

The kernel walks the tokens in tiles of ``tile`` (``default_tile``, a pure
function of the shape). Because the sort is stable, the held rows of one
tile and one expert are a contiguous RUN of ``a``, in token order; a
tile's runs are found before the launch from ``inv`` and ``sizes``
(``_runs``: small int32 passes over the [T, k] pairs, no sort and no
scatter). ``a`` stays in HBM, and each run is copied into a VMEM staging
slot as whole chunks of ``CHUNK`` rows (Mosaic copies no slice of a tiled
array that is not aligned to its tile: one row alone cannot be fetched),
so a tile reads its held rows and at most ``2 CHUNK - 2`` rows more a run.
The copies of the next tile start before the current tile's wait, into
the other of two slots, so one tile's reads run under the sum of the one
before. The sum is a 0/1 selection product on the MXU over the staged
rows, in blocks of ``BLOCK``: ``out_tile += S^T @ rows``, ``S[r, t]`` = 1
where a pick of token t is staged at row r; staged rows that no pick of
the tile selects (a neighbour in a chunk, a row past ``held``, a stale
row) are zeroed first, so nothing else leaks. Each product is exact and
summed in f32 (the order of the k terms is the MXU's, not the pick
order). Its cost follows the held picks: about ``held`` rows read (plus
the chunks' ends), the staged blocks' products, and the ``T`` f32 rows
of the result written once; no [T, k, d] array is made.

Each launch shape is traced once (``_launch``); ``tune.counters()
["pick_sums"]`` says what every traced call was built with. Kernels on the
TPU, interpret mode elsewhere. No reference equivalent: the 2018 codebase
has no expert layers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..place import on_tpu

CHUNK = 16      # rows a copy moves: a bf16 tile's sublanes
BLOCK = 128     # staged rows a selection product contracts
TILES = (256, 128)
# what Mosaic grants one kernel on a v5e unless told otherwise (its scoped
# vmem limit), and the most a launch asks for (half a v5e core's 128 MiB)
VMEM_LIMIT = 16 * 1024 * 1024
VMEM_MOST = 64 * 1024 * 1024
_F32, _I32 = jnp.float32, jnp.int32
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def staged_rows(tile, k, groups):
    """The most rows one tile stages: its ``tile x k`` picks and, for each
    of the ``groups`` runs, the two part-chunks at its ends, rounded up to
    a ``BLOCK``."""
    need = tile * k + groups * (2 * CHUNK - 2)
    return -(-need // BLOCK) * BLOCK


def vmem_bytes(tile, k, d, groups, itemsize):
    """VMEM a launch keeps at token tile ``tile``: the two staging slots,
    the result block twice (f32; a bf16 result's two blocks and its f32
    sum take as many bytes), the picks' staged rows ([k, tile] int32)
    twice, and one block's temporaries (the staged rows widened to f32,
    the selection in f32 and its operand copy)."""
    lanes = -(-d // 128) * 128
    return (2 * staged_rows(tile, k, groups) * lanes * itemsize
            + 2 * tile * lanes * 4 + 2 * 8 * tile * 4
            + BLOCK * lanes * (4 + itemsize) + 2 * BLOCK * tile * 4)


def default_tile(T, k, d, groups, itemsize):
    """The widest of ``TILES`` that divides ``T`` (padded to a multiple
    of 128 where none does) and whose ``vmem_bytes`` fits ``VMEM_MOST``;
    a call of at most 128 tokens is one tile. Raises where none fits."""
    if T <= 128:
        return T
    padded = -(-T // 128) * 128
    for tile in TILES:
        if padded % tile == 0 and vmem_bytes(
                tile, k, d, groups, itemsize) <= VMEM_MOST:
            return tile
    raise ValueError(
        "a pick sum of %d picks of width %d over %d runs needs %d B of VMEM "
        "at 128 tokens a tile, past the %d a launch may ask for"
        % (k, d, groups, vmem_bytes(128, k, d, groups, itemsize), VMEM_MOST))


def _runs(inv, sizes, tile, rows):
    """Each tile's runs and each pick's staged row. Returns ``table``
    int32 [tiles x 3 G] (3 G a tile; per held expert g: the first chunk of
    the tile's run, its chunks, the staged row it starts at) and ``pos``
    int32 [k, T] (the staged row of pick j of token t within its tile's
    slot, -1 where the pick is not held). Chunks are kept inside the
    ``rows`` of ``a``."""
    T, k = inv.shape
    groups = sizes.shape[0]
    tiles = T // tile
    sizes = sizes.astype(_I32)
    ends = jnp.cumsum(sizes)
    group = jnp.sum(inv[..., None] >= ends, axis=-1, dtype=_I32)
    onehot = group[..., None] == jnp.arange(groups, dtype=_I32)
    count = jnp.sum(onehot.reshape(tiles, tile * k, groups), axis=1,
                    dtype=_I32)
    start = ends - sizes + jnp.cumsum(count, axis=0) - count
    first = start // CHUNK
    chunks = jnp.where(count > 0, (start + count + CHUNK - 1) // CHUNK
                       - first, 0)
    first = jnp.minimum(first, rows // CHUNK - 1)
    chunks = jnp.minimum(chunks, rows // CHUNK - first)
    stage = (jnp.cumsum(chunks, axis=1) - chunks) * CHUNK
    shift = jnp.repeat(stage - first * CHUNK, tile, axis=0)     # [T, G]
    pos = jnp.where(group < groups, inv + jnp.sum(
        jnp.where(onehot, shift[:, None, :], 0), axis=-1), -1)
    table = jnp.concatenate([first, chunks, stage], axis=1)
    return table.reshape(-1), pos.T


def _kernel(table, pos_ref, a_hbm, out_ref, buf, sem, *acc_ref, groups, k,
            tiles, precision):
    """One token tile; ``acc_ref``: where the result is not f32, the f32
    sum the tile is added up in before its one cast."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    i = pl.program_id(0)
    slot = lax.rem(i, 2)
    width = 3 * groups

    def chunk_copy(chunk, into, row):
        return pltpu.make_async_copy(
            a_hbm.at[pl.ds(pl.multiple_of(chunk * CHUNK, CHUNK), CHUNK)],
            buf.at[into, pl.ds(pl.multiple_of(row, CHUNK), CHUNK)],
            sem.at[into])

    def start(at, into):         # the copies of the tile whose runs are at
        for g in range(groups):
            first, stage = table[at + g], table[at + 2 * groups + g]

            def copy(q, carry, first=first, stage=stage):
                chunk_copy(first + q, into, stage + q * CHUNK).start()
                return carry
            lax.fori_loop(0, table[at + groups + g], copy, _I32(0))

    now = i * width

    @pl.when(i == 0)
    def _first():
        start(now, 0)

    @pl.when(i + 1 < tiles)
    def _next():
        start(now + width, 1 - slot)

    chunks = table[now + groups]
    for g in range(1, groups):
        chunks = chunks + table[now + groups + g]

    def wait(_q, carry):
        chunk_copy(0, slot, 0).wait()
        return carry
    lax.fori_loop(0, chunks, wait, _I32(0))

    tile = out_ref.shape[0]
    acc = acc_ref[0] if acc_ref else out_ref
    acc[...] = jnp.zeros(acc.shape, _F32)
    pos = pos_ref[...]

    def block(b, carry):
        row0 = pl.multiple_of(b * BLOCK, BLOCK)
        staged = lax.broadcasted_iota(_I32, (BLOCK, tile), 0) + row0
        sel = jnp.zeros((BLOCK, tile), _F32)
        for j in range(k):
            sel = sel + (pos[j:j + 1, :] == staged).astype(_F32)
        kept = jnp.max(sel, axis=1, keepdims=True) > 0
        rows = buf[slot, pl.ds(row0, BLOCK), :]
        rows = jnp.where(kept, rows, jnp.zeros_like(rows))
        acc[...] += lax.dot_general(
            sel.astype(rows.dtype), rows, _TN, precision=precision,
            preferred_element_type=_F32)
        return carry
    lax.fori_loop(0, (chunks * CHUNK + BLOCK - 1) // BLOCK, block, _I32(0))
    if acc_ref:
        out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.lru_cache(maxsize=None)
def _launch(rows, T, k, d, groups, dtype, out_dtype, tile, interpret):
    """The kernel's launch at these shapes, traced ONCE: ``fn(a [rows, d],
    table [T / tile x 3 groups] int32, pos [k, T] int32) -> [T, d]
    out_dtype``. The runs' table is a scalar prefetch (in SMEM for the
    whole launch)."""
    import jax.extend
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    tiles = T // tile
    itemsize = jnp.dtype(dtype).itemsize
    precision = (lax.Precision.HIGHEST if jnp.dtype(dtype) == _F32
                 else lax.Precision.DEFAULT)
    body = functools.partial(_kernel, groups=groups, k=k, tiles=tiles,
                             precision=precision)
    need = vmem_bytes(tile, k, d, groups, itemsize)
    staged = staged_rows(tile, k, groups)
    acc = [] if jnp.dtype(out_dtype) == _F32 else [
        pltpu.VMEM((tile, d), _F32)]

    def fn(a, table, pos):
        return pl.pallas_call(
            body,
            out_shape=jax.ShapeDtypeStruct((T, d), out_dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(tiles,),
                in_specs=[pl.BlockSpec((k, tile), lambda i, t: (0, i)),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((tile, d), lambda i, t: (i, 0)),
                scratch_shapes=[pltpu.VMEM((2, staged, d), dtype),
                                pltpu.SemaphoreType.DMA((2,))] + acc),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=None if need <= VMEM_LIMIT
                else -(-need // 2 ** 20) * 2 ** 20),
            cost_estimate=pl.CostEstimate(
                flops=2 * T * k * d, transcendentals=0,
                bytes_accessed=T * k * d * itemsize
                + T * d * jnp.dtype(out_dtype).itemsize),
            interpret=interpret,
            name="pick_sum")(table, pos, a)

    traced = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((rows, d), dtype),
        jax.ShapeDtypeStruct((tiles * 3 * groups,), _I32),
        jax.ShapeDtypeStruct((k, T), _I32))
    return jax.extend.core.jaxpr_as_fun(traced)


def pick_sum(a, inv, sizes, out_dtype=_F32):
    """``a [rows, d]`` sorted rows, ``inv [T, k]`` int32 the row of each
    (token, pick) pair, ``sizes [G]`` the rows of each held expert, in the
    sorted order (``held = sum(sizes)``, at most ``rows``) -> ``[T, d]``
    ``out_dtype``, ``out[t] = sum over j with inv[t, j] < held of
    f32(a[inv[t, j]])``, summed in f32 and cast once. Counted in ``tune.counters()["pick_sums"]``. Tokens, rows and
    a width that the kernel's tiles do not divide are padded (the width to
    whole lanes of 128) and cut off again."""
    from .. import tune
    rows, d = a.shape
    T, k = inv.shape
    groups = sizes.shape[0]
    tile = default_tile(T, k, d, groups, a.dtype.itemsize)
    tune.count_pick_sum(T, k, d, rows, tile)
    inv = inv.astype(_I32)
    pad_t = -T % tile
    if pad_t:
        inv = jnp.pad(inv, ((0, pad_t), (0, 0)), constant_values=rows)
    pad_r, pad_d = -rows % CHUNK, -d % 128
    if pad_r or pad_d:
        a = jnp.pad(a, ((0, pad_r), (0, pad_d)))
    table, pos = _runs(inv, sizes, tile, rows + pad_r)
    launch = _launch(rows + pad_r, T + pad_t, k, d + pad_d, groups,
                     jnp.dtype(a.dtype), jnp.dtype(out_dtype), tile,
                     not on_tpu())
    out = launch(a, table, pos)[0]
    return out[:T, :d] if pad_t or pad_d else out


def reference(a, inv, held):
    """``pick_sum`` in jnp, for ``held`` rows: every pick's row gathered
    at once ([T, k, d]), those at or past ``held`` masked, the k summed in
    f32 in pick order (the form the kernel replaced; tests and
    ``tools/pick_sum_sweep.py`` compare with it)."""
    got = a[jnp.minimum(inv, a.shape[0] - 1)]
    got = jnp.where((inv < held)[..., None], got, 0)
    return got.astype(_F32).sum(axis=1)
