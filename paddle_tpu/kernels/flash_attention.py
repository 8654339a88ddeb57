"""Flash attention (forward + backward) as Pallas TPU kernels.

Forward streams k/v blocks through VMEM against a resident q block,
maintaining the online-softmax (running max / numerator / denominator)
decomposition, and emits the per-row logsumexp — so the [S, S] score matrix
never materialises in HBM. Backward is the FlashAttention-2 recompute
scheme as two Pallas kernels: a dK/dV kernel (grid over k blocks, loop over
q blocks) and a dQ kernel (grid over q blocks, loop over k blocks); every
score/probability tile lives only as a [block_q, block_k] VMEM tile.

How the three kernels tile the score matrix is one decision, made per
call from what the call can observe (``default_blocks``: lengths, head
widths, dtype, causal, and a reckoning of what each kernel keeps in VMEM,
``vmem_bytes``); a paddle_tpu.tune winner overrides it. Of a causal call's
tiles only those the diagonal crosses are masked and those above it are
never visited, whatever the two block widths; the padded-k mask is applied
only to the tiles that hold padded positions.

Grouped heads: k/v may have fewer heads than q (``H % Hkv == 0``); q head
j reads k/v head ``j // (H / Hkv)`` through the k/v blocks' index maps, so
k and v are never repeated in HBM, and a kernel whose grid steps through a
group's q heads finds the group's k/v already in VMEM. The dK/dV kernel
writes each q head's part in f32 and one sum over the group axis folds
them. ``window``: a causal call in which position i sees the keys j with
``0 <= i - j < window``; the tiles wholly under the band's lower edge are
never visited, those it crosses are masked, in all three kernels.

A sequence longer than one 128-wide tile runs at its length rounded up to
128 (``padded_len``), whatever the blocks: padded k positions are masked
inside the kernels; padded q rows are sliced off (and contribute exactly
zero to dK/dV because their dO rows are zero-padded).

The per-row statistics (lse, delta) reach the dK/dV kernel, which holds
them for the whole sequence, as lane-dense rows ``[BH, Sq / block_q,
block_q]``: that kernel works on the TRANSPOSED tile (``[block_k,
block_q]``: k @ q^T), where a q row's statistic is a ``[1, block_q]`` row
and both accumulations are plain products. As ``[BH, S, 1]`` columns they
took 512 B a position in VMEM (a (8, 128) f32 tile for 8 numbers): 8 MB
double-buffered at S = 4,096, half the scoped limit. The forward and dQ
kernels see one q block of them a grid step and keep the columns.

The logsumexp output is what lets parallel/ring.py chain per-ring-step
flash calls with the numerically exact merge
``o = (o_a * exp(lse_a - lse) + o_b * exp(lse_b - lse))`` — gradients flow
through both o and lse (the dlse term folds into the backward's delta).

A differentiated call's o and lse are the backward kernels' residuals,
named (``KEPT``) so that a checkpoint that recomputes the rest of a layer
keeps them (ops/generic_grad.py); the forward kernel of one shape is traced
once (``_forward_launch``), so the launch a gradient replays is the forward
op's own to XLA, which merges the two.

No reference equivalent (attention postdates the 2018 codebase); this is a
capability the TPU build adds, used by nets.scaled_dot_product_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..place import on_tpu

LANE = 128
NEG_INF = -1e30
KERNELS = ("fwd", "dq", "dkv")
# the names (jax.ad_checkpoint.checkpoint_name) of a differentiated call's
# o and lse: what its backward kernels read of the forward
KEPT = ("flash_o", "flash_lse")
BLOCK_WIDTHS = (512, 256, 128)
# what Mosaic grants one kernel on a v5e unless told otherwise (its
# "scoped vmem limit"); the reckoning keeps a shape under it
VMEM_LIMIT = 16 * 1024 * 1024
# the most a launch asks Mosaic for (half a v5e core's 128 MiB of VMEM),
# where no blocks fit the shape under VMEM_LIMIT: whole-sequence k and v of
# 16,384 positions of 128-wide heads are 16 MiB twice over alone
VMEM_MOST = 64 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))      # a @ b^T: contract both minor dims


def padded_len(S):
    """The length the kernels run S positions at: S itself up to one
    128-wide tile (the block is the whole array), else S rounded up to
    128. No choice of blocks adds to it."""
    return S if S <= LANE else -(-S // LANE) * LANE


def _block_bytes(rows, cols, itemsize):
    """VMEM bytes of a [rows, cols] block: lanes round up to 128, rows to
    the dtype's sublane tile (8 for f32, 16 for bf16)."""
    sub = 8 * 4 // itemsize
    return (-(-rows // sub) * sub) * (-(-cols // LANE) * LANE) * itemsize


def vmem_bytes(kernel, bq, bk, Sq, Sk, D, Dv, itemsize, group=1):
    """What ``kernel`` ("fwd", "dq" or "dkv") keeps in VMEM at blocks
    (bq, bk), by the kernel's own BlockSpecs: every operand and result
    block twice (the pipeline's two buffers; the whole-sequence operands
    too), the blocks widened to f32, the f32 accumulators, and the
    [bq, bk] f32 tiles live at once (scores, probabilities and, backward,
    dP and dS). It errs high: by 0.1-2.9 MiB against what Mosaic needed
    for a described v5e at the shapes tried, and no shape it grants was
    refused on the chip (PERF.md §6, PR 33; tests/test_chip_compile.py
    holds it to Mosaic's own refusals). ``group`` q heads to a k/v head
    change one thing: above 1 the dK/dV kernel's two result blocks are
    f32. A window changes nothing here (k and v stay whole in VMEM)."""
    blk = functools.partial(_block_bytes, itemsize=itemsize)
    f32 = functools.partial(_block_bytes, itemsize=4)
    if kernel == "fwd":
        piped = (blk(bq, D) + blk(Sk, D) + blk(Sk, Dv) + blk(bq, Dv)
                 + f32(bq, 1))
        wide = f32(bq, D) + f32(bk, D) + f32(bk, Dv) + 2 * f32(bq, Dv)
        tiles = 3
    elif kernel == "dq":
        piped = (2 * blk(bq, D) + blk(Sk, D) + blk(Sk, Dv) + blk(bq, Dv)
                 + 2 * f32(bq, 1))
        wide = (2 * f32(bq, D) + f32(bq, Dv) + f32(bk, D) + f32(bk, Dv))
        tiles = 4
    elif kernel == "dkv":
        out = f32 if group > 1 else blk
        piped = (blk(Sq, D) + blk(Sq, Dv) + blk(bk, D) + blk(bk, Dv)
                 + out(bk, D) + out(bk, Dv) + 2 * f32(Sq // bq, bq))
        wide = (f32(bq, D) + f32(bq, Dv) + 2 * f32(bk, D)
                + 2 * f32(bk, Dv))
        tiles = 4
    else:
        raise ValueError("no flash kernel %r" % (kernel,))
    return 2 * piped + wide + tiles * f32(bq, bk)


def vmem_budget(kernel, Sq, Sk, D, Dv, itemsize, group=1):
    """What ``kernel``'s blocks may keep in VMEM at these lengths and
    widths: VMEM_LIMIT where its narrowest blocks fit under it (every
    shape of 8,192 positions or fewer the cells run), else VMEM_MOST
    (rows of 16,384: the whole-sequence operands alone exceed the
    default)."""
    narrow = [min([w for w in BLOCK_WIDTHS if S % w == 0] or [S])
              for S in (Sq, Sk)]
    fits = vmem_bytes(kernel, *narrow, Sq, Sk, D, Dv, itemsize,
                      group) <= VMEM_LIMIT
    return VMEM_LIMIT if fits else VMEM_MOST


def vmem_limit(kernel, bq, bk, Sq, Sk, D, Dv, itemsize, group=1):
    """The scoped VMEM a launch at blocks (bq, bk) asks Mosaic for: None
    (Mosaic's default, VMEM_LIMIT) where ``vmem_bytes`` fits under it,
    else the reckoning rounded up to a MiB."""
    need = vmem_bytes(kernel, bq, bk, Sq, Sk, D, Dv, itemsize, group)
    return None if need <= VMEM_LIMIT else -(-need // 2 ** 20) * 2 ** 20


def _fits(kernel, bq, bk, Sq, Sk, D, Dv, itemsize, group=1):
    return vmem_bytes(kernel, bq, bk, Sq, Sk, D, Dv, itemsize,
                      group) <= vmem_budget(kernel, Sq, Sk, D, Dv, itemsize,
                                            group)


def _compiler_params(kernel, bq, bk, Sq, Sk, D, Dv, itemsize, group):
    """``pallas_call``'s ``compiler_params`` of a launch: the raised scoped
    VMEM where ``vmem_limit`` asks for it, else none (the launch is built
    as it was before any was raised)."""
    limit = vmem_limit(kernel, bq, bk, Sq, Sk, D, Dv, itemsize, group)
    if limit is None:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=limit)}


def default_blocks(kernel, Sq, Sk, D, Dv, dtype, causal, group=1,
                   window=None):
    """(block_q, block_k) of ``kernel`` for a call of padded lengths
    (Sq, Sk): a pure function of the call's shape, read off the sweep in
    PERF.md §6 (PR 33). On each side the widest of 512 / 256 / 128 that
    divides the length (so a block never adds padding; a sequence under
    128 is one block), at every length and head width the sweep tried,
    causal or not: the rule has no length threshold. Where ``vmem_bytes``
    says Mosaic would refuse the pair it falls back a size, narrowing
    first the side the kernel's grid walks (q for forward and dQ, k for
    dK/dV) and keeping wide the side its inner loop walks, whose trips
    are what a tile's fixed cost is paid on. Under a ``window`` no block is
    wider than the window rounded up to 128: a band narrower than its
    tiles is mostly mask (a 2,048-key window leaves the rule as it is).
    "Fits" is under ``vmem_budget``: where no blocks fit VMEM_LIMIT (rows
    of 16,384) the walk runs under VMEM_MOST and the launch asks for what
    its blocks need."""
    del causal  # the sweep ranks the shapes alike causal and not
    itemsize = jnp.dtype(dtype).itemsize
    cap = max(BLOCK_WIDTHS) if window is None else max(
        LANE, -(-window // LANE) * LANE)
    q_widths, k_widths = (
        [w for w in BLOCK_WIDTHS if S % w == 0 and w <= cap] or [S]
        for S in (Sq, Sk))
    grid_is_q = kernel != "dkv"
    grid, loop = (q_widths, k_widths) if grid_is_q else (k_widths, q_widths)
    gi = li = 0
    while True:
        pair = (grid[gi], loop[li])
        bq, bk = pair if grid_is_q else pair[::-1]
        last_g, last_l = gi == len(grid) - 1, li == len(loop) - 1
        if (last_g and last_l) or _fits(kernel, bq, bk, Sq, Sk, D, Dv,
                                        itemsize, group):
            return bq, bk
        if not last_g and (grid[gi] >= loop[li] or last_l):
            gi += 1
        else:
            li += 1


def _chosen_blocks(kernel, config, q3, k3, v3, causal, window):
    """The blocks ``kernel`` runs this call at: a paddle_tpu.tune
    "flash_attention" winner ({block_q, block_k}, clamped to the lengths)
    where it divides the padded lengths and fits VMEM, else the rule — a
    stale or refused cache entry degrades, it never fails."""
    (BH, Sq, D), Sk, Dv = q3.shape, k3.shape[1], v3.shape[2]
    group = BH // k3.shape[0]
    cfg = dict(config) if config else {}
    bq = min(int(cfg.get("block_q", 0)), Sq)
    bk = min(int(cfg.get("block_k", 0)), Sk)
    if bq < 1 or bk < 1 or Sq % bq or Sk % bk or not _fits(
            kernel, bq, bk, Sq, Sk, D, Dv, q3.dtype.itemsize, group):
        bq, bk = default_blocks(kernel, Sq, Sk, D, Dv, q3.dtype, causal,
                                group, window)
    return bq, bk


def _blocks(kernel, config, q3, k3, v3, causal, valid_len, window):
    """``_chosen_blocks``, counting the choice and the tiles it makes the
    launch visit (``tune.counters()["flash_blocks"]`` /
    ``["flash_tiles"]``)."""
    from .. import tune
    bq, bk = _chosen_blocks(kernel, config, q3, k3, v3, causal, window)
    tune.count_flash_blocks(
        kernel, bq, bk, q3.shape[0] // k3.shape[0], window,
        tile_counts(kernel, bq, bk, q3.shape[1], k3.shape[1], valid_len,
                    causal, window))
    return bq, bk


def _dense_reference(q, k, v, causal, scale, window=None):
    """q [BH, S, D] against k/v [BHkv, S, .]: the dense composition (a
    group's k/v head repeated, the band a mask)."""
    group = q.shape[0] // k.shape[0]
    if group > 1:
        k, v = (jnp.repeat(a, group, axis=0) for a in (k, v))
    s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((S, S), bool), -window)
        s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


# ---------------------------------------------------------------------------
# the score tile and its masks


def _masked_scores(q, k_blk, q_start, k_start, *, scale, causal=False,
                   valid_len=None, window=None, transposed=False):
    """Scaled score tile q @ k^T ([bq, bk]; ``transposed``: k @ q^T, [bk,
    bq]) with the causal mask, where ``window`` is given the band's lower
    edge (qpos - kpos < window) and, where ``valid_len`` is given, the
    padded-k mask — the single source of masking truth shared by forward
    and both backward kernels (they must never disagree). A kernel asks
    for a mask only on the tiles that need it (``_k_segments``,
    ``_q_segments``)."""
    a, b = (k_blk, q) if transposed else (q, k_blk)
    s = jax.lax.dot_general(a, b, _NT,
                            preferred_element_type=jnp.float32) * scale
    q_axis, k_axis = (1, 0) if transposed else (0, 1)
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32, s.shape)
    if causal:
        # qpos >= kpos: a loop-invariant difference against one scalar
        s = jnp.where(iota(q_axis) - iota(k_axis) >= k_start - q_start, s,
                      NEG_INF)
    if window is not None:
        s = jnp.where(
            iota(q_axis) - iota(k_axis) < window + k_start - q_start, s,
            NEG_INF)
    if valid_len is not None:
        s = jnp.where(iota(k_axis) < valid_len - k_start, s, NEG_INF)
    return s


def _least(a, b):
    both = isinstance(a, int) and isinstance(b, int)
    return min(a, b) if both else jnp.minimum(a, b)


def _most(a, b):
    both = isinstance(a, int) and isinstance(b, int)
    return max(a, b) if both else jnp.maximum(a, b)


def _k_segments(q_start, bq, bk, kv_len, valid_len, causal, window=None):
    """The k tiles (``bk`` wide) a q block at rows [q_start, q_start + bq)
    visits (forward, dQ), in order, as (masks, first, end) runs; ``masks``
    are ``_masked_scores``' keywords for the run's tiles. Causal: the tiles
    wholly at or under the block's first row need no mask, those the
    diagonal crosses do, and none beyond the block's last row is visited,
    whatever the two widths. The tiles that hold padded k positions are
    masked ones: causal, they are among those the diagonal crosses (q and
    k share their padding); not causal, they are the last. ``window``
    (causal only): no tile wholly under the band's lower edge (every key
    ``window`` or more behind the block's first row) is visited, and the
    tiles that hold a pair ``window`` or more apart carry the band's mask;
    a window narrower than ``bq + bk - 2`` can put both edges on one
    tile."""
    n_k = kv_len // bk
    pad = valid_len if valid_len < kv_len else None
    if not causal:
        clear = n_k if pad is None else valid_len // bk
        return ({}, 0, clear), ({"valid_len": pad}, clear, n_k)
    clear = (q_start + 1) // bk
    end = -(-(q_start + bq) // bk)
    diagonal = {"causal": True, "valid_len": pad}
    if window is None:
        return ({}, 0, clear), (diagonal, clear, end)
    band = {"window": window}
    first = _most(q_start - window + 1, 0) // bk
    banded = _most(q_start + bq - 1 - window + bk, 0) // bk
    if window >= bq + bk - 2:           # banded <= clear, at every block
        return (band, first, banded), ({}, banded, clear), (diagonal, clear,
                                                            end)
    return ((band, first, _least(banded, clear)), ({}, banded, clear),
            (dict(diagonal, **band), clear, banded),
            (diagonal, _most(banded, clear), end))


def _q_segments(k_start, bk, bq, n_q, causal, pad, window=None):
    """The q tiles (``bq`` wide) a k block at positions [k_start, k_start
    + bk) visits (dK/dV), as ``_k_segments`` gives them: causal, from the
    q tile that holds its first position, masked up to the first q tile
    wholly at or under its last position, clear from there on; to the end,
    or under a ``window`` to the last q tile that holds a row less than
    ``window`` past its last position, the band's mask on the tiles that
    hold a pair ``window`` or more apart.
    ``pad``: the valid length where the block holds padded positions
    (every tile it visits then masks them), else None."""
    first = k_start // bq if causal else 0
    end = n_q
    band = {}
    if window is not None:
        end = _least(n_q, (k_start + bk + window - 2) // bq + 1)
        band = {"window": window}
    if pad is not None:
        return ((dict(causal=causal, valid_len=pad, **band), first, end),)
    clear = -(-(k_start + bk - 1) // bq) if causal else 0
    if window is None:
        return ({"causal": True}, first, clear), ({}, clear, end)
    banded = (k_start + window) // bq       # first tile the band crosses
    if window >= bq + bk - 2:               # clear <= banded, at every block
        return (({"causal": True}, first, clear),
                ({}, clear, _least(banded, end)), (band, banded, end))
    return (({"causal": True}, first, _least(clear, banded)),
            ({}, clear, _least(banded, end)),
            (dict(band, causal=True), banded, clear),
            (band, _most(banded, clear), end))


def tile_counts(kernel, bq, bk, Sq, Sk, valid_len, causal, window=None):
    """{"visited", "masked", "square"}: the score tiles one head's launch
    of ``kernel`` visits, those of them that carry a mask, and the tiles
    of the whole [Sq, Sk] square, by the kernels' own segments."""
    n_q, n_k = Sq // bq, Sk // bk
    if kernel == "dkv":
        runs = (run for ki in range(n_k) for run in _q_segments(
            ki * bk, bk, bq, n_q, causal,
            valid_len if (ki + 1) * bk > valid_len else None, window))
    else:
        runs = (run for qi in range(n_q) for run in _k_segments(
            qi * bq, bq, bk, Sk, valid_len, causal, window))
    visited = masked = 0
    for masks, lo, hi in runs:
        visited += max(hi - lo, 0)
        if any(v not in (None, False) for v in masks.values()):
            masked += max(hi - lo, 0)
    return {"visited": visited, "masked": masked, "square": n_q * n_k}


def _run_tiles(segments, step, acc):
    """One fori_loop of ``step(masks)`` per run of tiles; a run that is
    empty at trace time makes none."""
    for masks, lo, hi in segments:
        if not (isinstance(lo, int) and isinstance(hi, int) and lo >= hi):
            acc = jax.lax.fori_loop(lo, hi, step(masks), acc)
    return acc


# ---------------------------------------------------------------------------
# forward kernel: one q block vs streamed k/v blocks -> o block + lse rows


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, *, causal, scale, block_k,
               kv_len, valid_len, window):
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32)              # [block_q, D]
    bq = q.shape[0]
    dv = v_ref.shape[-1]                          # v heads may be narrower
    q_start = pl.program_id(1) * bq

    def step(masks):
        def body(ki, acc):
            m, num, den = acc
            k_start = ki * block_k
            k_blk = k_ref[0, pl.ds(k_start, block_k), :].astype(jnp.float32)
            v_blk = v_ref[0, pl.ds(k_start, block_k), :].astype(jnp.float32)
            s = _masked_scores(q, k_blk, q_start, k_start, scale=scale,
                               **masks)
            new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - new_m)
            alpha = jnp.exp(m - new_m)
            num = num * alpha + jnp.dot(
                p, v_blk, preferred_element_type=jnp.float32)
            den = den * alpha + jnp.sum(p, axis=-1, keepdims=True)
            return new_m, num, den
        return body

    # per-row stats stay [bq, 1] columns (sublane-major, like the score
    # tile's rows) from the loop carry to the lse store
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    num0 = jnp.zeros((bq, dv), jnp.float32)
    den0 = jnp.zeros((bq, 1), jnp.float32)
    m, num, den = _run_tiles(
        _k_segments(q_start, bq, block_k, kv_len, valid_len, causal, window),
        step, (m0, num0, den0))
    den_safe = jnp.maximum(den, 1e-20)
    o_ref[0] = (num / den_safe).astype(o_ref.dtype)
    l_ref[0] = m + jnp.log(den_safe)


def _kv_head(group):
    """The k/v head (row of k3 / v3) that q head ``b`` of the grid reads."""
    return (lambda b: b) if group == 1 else (lambda b: b // group)


def _fa_forward(q3, k3, v3, causal, scale, valid_len, interpret,
                config=None, window=None):
    """q3 [BH, Sq, D], k3 [BHkv, Sk, D], v3 [BHkv, Sk, Dv] -> (o [BH, Sq,
    Dv], lse [BH, Sq]): the scores use D, the values and the output Dv
    (latent attention has 192-wide q/k heads and 128-wide v heads); q head
    b reads k/v head b // (BH / BHkv).
    Sq may differ from Sk (ring-attention block chaining); causal requires
    Sq == Sk (aligned positions).

    The lse leaves the kernel as a [BH, Sq, 1] column: Mosaic wants a
    block's last two dims divisible by (8, 128) or equal to the array's,
    and (block_q, 1) over [Sq, 1] is — the kernel's running statistics
    are [block_q, 1] columns already."""
    block_q, block_k = _blocks("fwd", config, q3, k3, v3, causal, valid_len,
                               window)
    launch = _forward_launch(tuple((a.shape, a.dtype) for a in (q3, k3, v3)),
                             causal, scale, valid_len, interpret, block_q,
                             block_k, window)
    o, lse = launch(q3, k3, v3)
    return o, lse[:, :, 0]


@functools.lru_cache(maxsize=64)
def _forward_launch(shapes, causal, scale, valid_len, interpret, block_q,
                    block_k, window):
    """The forward kernel's launch at these (shape, dtype)s of q3 / k3 / v3
    and these statics, traced ONCE: ``fn(q3, k3, v3) -> (o, lse [BH, Sq,
    1])``. A Pallas kernel carries the source locations of the trace that
    built it, and XLA merges two launches on the same operands only where
    their kernels are the same text. Built once, the forward op's launch
    and the one that a recomputing op's gradient replays beside it
    (``ops/generic_grad.py``) are one launch on the device, whose ``o``
    and ``lse`` the backward kernels read."""
    from jax.experimental import pallas as pl
    import jax.extend

    ((BH, Sq, D), dtype), ((BHkv, Sk, _D), _), ((_, _, Dv), _) = shapes
    kv = _kv_head(BH // BHkv)
    params = _compiler_params("fwd", block_q, block_k, Sq, Sk, D, Dv,
                              jnp.dtype(dtype).itemsize, BH // BHkv)
    kernel = functools.partial(_fa_kernel, causal=causal, scale=scale,
                               block_k=block_k, kv_len=Sk,
                               valid_len=valid_len, window=window)
    call = pl.pallas_call(
        kernel,
        grid=(BH, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (kv(b), 0, 0)),
            pl.BlockSpec((1, Sk, Dv), lambda b, i: (kv(b), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, Dv), dtype),
            jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32),
        ],
        interpret=interpret,
        **params)
    traced = jax.make_jaxpr(call)(
        *(jax.ShapeDtypeStruct(s, d) for s, d in shapes))
    return jax.extend.core.jaxpr_as_fun(traced)


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2 recompute scheme)
#
# With p = exp(s - lse):  dv = p^T dO;  dp = dO v^T;
# ds = p * (dp - delta) * scale where delta = rowsum(dO * o) - dlse;
# dq = ds k;  dk = ds^T q.  All tiles [block_q, block_k] in VMEM; the
# dK/dV kernel holds their transposes, so p^T dO and ds^T q are plain
# products and lse / delta are [1, block_q] rows.


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, dl_ref,
                       dk_ref, dv_ref, *, causal, scale, block_q,
                       q_len, kv_len, valid_len, window):
    from jax.experimental import pallas as pl

    k_blk = k_ref[0].astype(jnp.float32)          # [block_k, D]
    v_blk = v_ref[0].astype(jnp.float32)          # [block_k, Dv]
    bk, d = k_blk.shape
    k_start = pl.program_id(1) * bk
    n_q = q_len // block_q

    def step(masks):
        def body(qi, acc):
            dk, dv = acc
            q_start = qi * block_q
            q = q_ref[0, pl.ds(q_start, block_q), :].astype(jnp.float32)
            do = do_ref[0, pl.ds(q_start, block_q), :].astype(jnp.float32)
            lse = l_ref[0, pl.ds(qi, 1), :]                   # [1, bq]
            delta = dl_ref[0, pl.ds(qi, 1), :]
            st = _masked_scores(q, k_blk, q_start, k_start, scale=scale,
                                transposed=True, **masks)     # [bk, bq]
            pt = jnp.exp(st - lse)
            dv = dv + jnp.dot(pt, do, preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(v_blk, do, _NT,
                                      preferred_element_type=jnp.float32)
            dst = pt * (dpt - delta) * scale
            dk = dk + jnp.dot(dst, q, preferred_element_type=jnp.float32)
            return dk, dv
        return body

    acc0 = (jnp.zeros((bk, d), jnp.float32),
            jnp.zeros((bk, v_blk.shape[1]), jnp.float32))

    def run(pad):
        return _run_tiles(
            _q_segments(k_start, bk, block_q, n_q, causal, pad, window),
            step, acc0)

    if valid_len < kv_len:
        dk, dv = jax.lax.cond(k_start + bk > valid_len,
                              lambda: run(valid_len), lambda: run(None))
    else:
        dk, dv = run(None)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, dl_ref,
                      dq_ref, *, causal, scale, block_k, kv_len,
                      valid_len, window):
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32)              # [block_q, D]
    do = do_ref[0].astype(jnp.float32)            # [block_q, Dv]
    lse = l_ref[0]                                # [block_q, 1]
    delta = dl_ref[0]
    bq, d = q.shape
    q_start = pl.program_id(1) * bq

    def step(masks):
        def body(ki, dq):
            k_start = ki * block_k
            k_blk = k_ref[0, pl.ds(k_start, block_k), :].astype(jnp.float32)
            v_blk = v_ref[0, pl.ds(k_start, block_k), :].astype(jnp.float32)
            s = _masked_scores(q, k_blk, q_start, k_start, scale=scale,
                               **masks)
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(do, v_blk, _NT,
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            return dq + jnp.dot(ds, k_blk,
                                preferred_element_type=jnp.float32)
        return body

    dq = _run_tiles(
        _k_segments(q_start, bq, block_k, kv_len, valid_len, causal, window),
        step, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _fa_backward(q3, k3, v3, do3, lse, delta, causal, scale, valid_len,
                 interpret, config=None, window=None):
    """lse / delta [BH, Sq] -> (dq, dk, dv). Each kernel tiles by its own
    blocks; the dK/dV kernel takes the statistics as rows of its block_q,
    the dQ kernel as columns (module text). Grouped heads: the dK/dV
    kernel's grid is over the q heads (each holds its own q and dO whole
    and reads its group's k/v block), its results are per q head in f32,
    and one sum over the group axis makes dk and dv."""
    from jax.experimental import pallas as pl

    BH, Sq, D = q3.shape
    BHkv, Sk, Dv = k3.shape[0], k3.shape[1], v3.shape[2]
    group = BH // BHkv
    kv = _kv_head(group)
    block_q, block_k = _blocks("dkv", config, q3, k3, v3, causal, valid_len,
                               window)
    itemsize = q3.dtype.itemsize
    rows = (BH, Sq // block_q, block_q)
    row_spec = pl.BlockSpec((1,) + rows[1:], lambda b, i: (b, 0, 0))
    part = jnp.float32 if group > 1 else None
    dkv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, causal=causal, scale=scale,
                          block_q=block_q, q_len=Sq, kv_len=Sk,
                          valid_len=valid_len, window=window),
        grid=(BH, Sk // block_k),
        in_specs=[
            pl.BlockSpec((1, Sq, D), lambda b, i: (b, 0, 0)),     # q
            pl.BlockSpec((1, block_k, D), lambda b, i: (kv(b), i, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, i: (kv(b), i, 0)),
            pl.BlockSpec((1, Sq, Dv), lambda b, i: (b, 0, 0)),    # do
            row_spec,                                             # lse
            row_spec,                                             # delta
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), part or k3.dtype),
            jax.ShapeDtypeStruct((BH, Sk, Dv), part or v3.dtype),
        ],
        interpret=interpret,
        **_compiler_params("dkv", block_q, block_k, Sq, Sk, D, Dv, itemsize,
                           group),
    )(q3, k3, v3, do3, lse.reshape(rows), delta.reshape(rows))
    if group > 1:
        dkv = [a.reshape(BHkv, group, Sk, a.shape[2]).sum(axis=1).astype(
            like.dtype) for a, like in zip(dkv, (k3, v3))]
    block_q, block_k = _blocks("dq", config, q3, k3, v3, causal, valid_len,
                               window)
    col_spec = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, causal=causal, scale=scale,
                          block_k=block_k, kv_len=Sk, valid_len=valid_len,
                          window=window),
        grid=(BH, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),  # q blk
            pl.BlockSpec((1, Sk, D), lambda b, i: (kv(b), 0, 0)),  # k
            pl.BlockSpec((1, Sk, Dv), lambda b, i: (kv(b), 0, 0)),  # v
            pl.BlockSpec((1, block_q, Dv), lambda b, i: (b, i, 0)),  # do blk
            col_spec,                                             # lse
            col_spec,                                             # delta
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q3.dtype),
        interpret=interpret,
        **_compiler_params("dq", block_q, block_k, Sq, Sk, D, Dv, itemsize,
                           group),
    )(q3, k3, v3, do3, lse[:, :, None], delta[:, :, None])
    return dq, dkv[0], dkv[1]


# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q3, k3, v3, causal, scale, valid_len, config=None, window=None):
    """q [BH, S, D], k [BHkv, S, D], v [BHkv, S, Dv] -> (o [BH, S, Dv],
    lse [BH, S]); S = padded_len(the call's length)."""
    return _fa_forward(q3, k3, v3, causal, scale, valid_len,
                       interpret=not on_tpu(), config=config, window=window)


def _flash_fwd(q3, k3, v3, causal, scale, valid_len, config=None,
               window=None):
    """The differentiated call: its ``o`` and ``lse`` are the residuals
    the backward kernels read, named (``KEPT``) so that a checkpoint's
    policy can keep them where it recomputes the rest
    (ops/generic_grad.py), and counted (``tune.counters()["flash_kept"]``
    / ``["flash_kept_bytes"]``)."""
    from .. import tune
    o, lse = _flash(q3, k3, v3, causal, scale, valid_len, config, window)
    o, lse = checkpoint_name(o, KEPT[0]), checkpoint_name(lse, KEPT[1])
    bq, bk = _chosen_blocks("fwd", config, q3, k3, v3, causal, window)
    tune.count_flash_kept(bq, bk, q3.shape[0] // k3.shape[0], window,
                          o.size * o.dtype.itemsize
                          + lse.size * lse.dtype.itemsize)
    return (o, lse), (q3, k3, v3, o, lse)


def _flash_bwd(causal, scale, valid_len, config, window, res, cots):
    q3, k3, v3, o, lse = res
    do3, dlse = cots
    # delta folds the lse cotangent: ds = p * (dp - rowsum(do*o) + dlse)
    delta = jnp.einsum("bsd,bsd->bs", do3.astype(jnp.float32),
                       o.astype(jnp.float32))
    if dlse is not None:
        delta = delta - dlse
    dq, dk, dv = _fa_backward(q3, k3, v3, do3, lse, delta, causal, scale,
                              valid_len, interpret=not on_tpu(),
                              config=config, window=window)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _heads_first(x, S_pad):
    """[B, S, H, D] -> [B * H, S_pad, D], zero rows past S."""
    B, S, H, D = x.shape
    if S != S_pad:
        x = jnp.pad(x, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
    return x.transpose(0, 2, 1, 3).reshape(B * H, S_pad, D)


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             config=None, window=None):
    """q: [batch, seq, heads, D], k: [batch, seq, kv heads, D], v: [batch,
    seq, kv heads, Dv] (Dv may differ from D; ``heads`` a multiple of ``kv
    heads``: q head j reads k/v head j // (heads / kv heads)) -> (out [B,
    S, H, Dv], lse [B, H, S]). ``scale`` multiplies the scores; None means
    D ** -0.5. ``window`` (causal calls): position i sees the keys j with
    0 <= i - j < window; None is the whole triangle.

    Any sequence length: S pads up to ``padded_len(S)`` internally; padded
    k positions are masked inside the kernels and padded q rows sliced off.
    The lse output makes per-block results mergeable (ring attention).
    ``config`` is a paddle_tpu.tune "flash_attention" pick
    ({block_q, block_k}); None leaves the blocks to ``default_blocks``.
    """
    B, S, H, D = q.shape
    Sk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    if causal and S != Sk:
        raise ValueError("causal flash attention needs q/k aligned lengths")
    if H % Hkv or v.shape[2] != Hkv:
        raise ValueError("%d q heads cannot share %d k and %d v heads"
                         % (H, Hkv, v.shape[2]))
    if window is not None and (not causal or int(window) < 1):
        raise ValueError("a window (%r) is a causal call's, and at least 1"
                         % (window,))
    scale = scale if scale is not None else D ** -0.5
    S_pad, Sk_pad = padded_len(S), padded_len(Sk)
    frozen = tuple(sorted(dict(config).items())) if config else None
    o3, lse = _flash(_heads_first(q, S_pad), _heads_first(k, Sk_pad),
                     _heads_first(v, Sk_pad), causal, float(scale), Sk,
                     frozen, None if window is None else int(window))
    o = o3.reshape(B, H, S_pad, Dv)[:, :, :S].transpose(0, 2, 1, 3)
    return o, lse.reshape(B, H, S_pad)[:, :, :S]


def flash_attention(q, k, v, causal=False, scale=None, config=None,
                    window=None):
    """q: [batch, seq, heads, D], k / v: [batch, seq, kv heads, D / Dv] ->
    [batch, seq, heads, Dv].

    Pallas streamed-softmax forward on TPU (interpret mode elsewhere),
    Pallas recompute backward (dq/dk/dv kernels) — no [S, S] buffer in
    either direction, any sequence length; grouped k/v heads and a sliding
    ``window`` as ``flash_attention_with_lse`` says."""
    return flash_attention_with_lse(q, k, v, causal, scale, config,
                                    window)[0]
