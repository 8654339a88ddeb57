"""Paged attention for the decode step as a Pallas TPU kernel.

The generation engine's fused decode step (models/transformer.decode_step)
reads each running row's K/V cache *through its block table* — and the
stock lowering does that with a gather that materialises
``[R, max_blocks, T, nh, dh]`` per layer before masking. The page-pool
layout (``[num_pages+1, T, nh, dh]`` per layer, last page = trash sink)
was shaped for this kernel instead: grid over (row blocks, kv page
blocks), the block-table indirection resolved *inside* the kernel by
scalar-prefetching the tables and letting each page's BlockSpec index
map pick its pool page — so only ``block_r * block_kv`` pages are ever
resident and the gather never exists.

Softmax is the online (running max / numerator / denominator)
decomposition accumulated in f32 VMEM scratch across the kv grid
dimension; columns past a row's position mask to ``NEG_INF`` so they
contribute exp(·)→0 exactly like the reference path's ``exp(-inf)=0``.
In decode, column 0 is always a real position (positions are >= 0), so
the running max is finite from the first tile and fully-trash later
tiles are self-correcting no-ops. Rows parked entirely on the trash
page compute attention over trash — the same garbage the gather
reference computes — and their outputs are discarded by the engine, so
parity holds on every row.

Interpret-mode capable (``interpret=not on_tpu()``), so the parity
grid in tests/test_kernels_parity.py is tier-1-testable on CPU. The
config contract is the conv3x3/flash contract: a stale or invalid tune
pick DEGRADES to the gather reference (``resolve_block_config`` ->
None), it never fails a trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..place import on_tpu

NEG_INF = -1e30

# candidate 0 of the search space AND the dispatch default: one row,
# one page per grid step — always legal for any pool geometry
DEFAULT_CONFIG = {"block_r": 1, "block_kv": 1}

# hard cap on block_r * block_kv: each (row, page) pair is one pallas
# input ref (the same pool array passed with a different index map), and
# an unbounded product would explode both the operand list and VMEM
MAX_PAGES_RESIDENT = 16


def population_key(max_running, max_blocks, page_tokens, num_heads,
                   head_dim, dtype="float32"):
    """The ONE encoding of a paged-attention shape key — shared by the
    engine's dispatch lookup, the tune CLI's artifact walk, and the
    space's tests, so cache signatures can never drift."""
    return {"r": int(max_running), "mb": int(max_blocks),
            "t": int(page_tokens), "nh": int(num_heads),
            "dh": int(head_dim), "dtype": str(dtype)}


def resolve_block_config(config, R, max_blocks):
    """Resolve ``(block_r, block_kv)`` for a call shape, or ``None``
    when the config cannot tile this geometry — the caller degrades to
    the gather reference. This is the single static validator: an
    invalid or stale winner-cache pick can slow a step down, never
    break one."""
    if config is None:
        return None
    cfg = dict(DEFAULT_CONFIG)
    try:
        cfg.update(dict(config))
        br = int(cfg["block_r"])
        bkv = int(cfg["block_kv"])
    except (TypeError, ValueError, KeyError):
        return None
    if br < 1 or bkv < 1 or br * bkv > MAX_PAGES_RESIDENT:
        return None
    if R % br or max_blocks % bkv:
        return None
    return br, bkv


def paged_attention_reference(q, k_pages, v_pages, block_tables,
                              positions):
    """The stock gather path — decode_step's attention math verbatim:
    gather ``[R, max_blocks, T, nh, dh]`` through the tables, mask
    columns past each row's position to -inf, dense softmax. The
    always-legal default the kernel is parity-gated against."""
    R, nh, dh = q.shape
    T = k_pages.shape[1]
    C = block_tables.shape[1] * T
    kc = k_pages[block_tables].reshape(R, C, nh, dh)
    vc = v_pages[block_tables].reshape(R, C, nh, dh)
    s = jnp.einsum("rhd,rchd->rhc", q, kc) * dh ** -0.5
    colmask = (jnp.arange(C, dtype=jnp.int32)[None, :]
               <= positions.astype(jnp.int32)[:, None])
    s = jnp.where(colmask[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("rhc,rchd->rhd", p, vc)


def _pa_kernel(tables_ref, pos_ref, q_ref, *refs, block_r, block_kv, T,
               scale, n_blocks):
    """One (row block, kv block) grid step: fold block_kv pages per row
    into the online-softmax scratch; emit on the last kv block."""
    from jax.experimental import pallas as pl

    nkv = block_r * block_kv
    k_refs = refs[:nkv]
    v_refs = refs[nkv:2 * nkv]
    out_ref = refs[2 * nkv]
    m_ref, num_ref, den_ref = refs[2 * nkv + 1:]
    rb = pl.program_id(0)
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    # Single-query attention is a matrix-VECTOR product per head: every
    # K/V element meets exactly one multiply-add, so the step is bound by
    # the page read, not the MXU. The contraction is therefore written as
    # a VPU broadcast-multiply + reduce with the head axis kept on
    # sublanes throughout ("hd,thd->ht" batches over h with no free lhs
    # dim — a dot form Mosaic has no dimension numbers for). Scores and
    # the running stats stay [.., nh, 1] columns: no relayout between
    # the score tile, the softmax and the p*V accumulation.
    for i in range(block_r):
        row = rb * block_r + i
        pos = pos_ref[row]
        q = q_ref[i].astype(jnp.float32)                   # [nh, dh]
        m, num, den = m_ref[i], num_ref[i], den_ref[i]
        nh = q.shape[0]
        for j in range(block_kv):
            slot = b * block_kv + j
            k_blk = k_refs[i * block_kv + j][0].astype(jnp.float32)
            v_blk = v_refs[i * block_kv + j][0].astype(jnp.float32)
            kvpos = slot * T + jax.lax.broadcasted_iota(
                jnp.int32, (T, nh, 1), 0)
            s = jnp.sum(k_blk * q[None], axis=-1,
                        keepdims=True) * scale              # [T, nh, 1]
            s = jnp.where(kvpos <= pos, s, NEG_INF)
            new_m = jnp.maximum(m, jnp.max(s, axis=0))      # [nh, 1]
            p = jnp.exp(s - new_m[None])                    # [T, nh, 1]
            alpha = jnp.exp(m - new_m)
            num = num * alpha + jnp.sum(p * v_blk, axis=0)  # [nh, dh]
            den = den * alpha + jnp.sum(p, axis=0)
            m = new_m
        m_ref[i], num_ref[i], den_ref[i] = m, num, den

    @pl.when(b == n_blocks - 1)
    def _emit():
        for i in range(block_r):
            den = jnp.maximum(den_ref[i], 1e-20)
            out_ref[i] = (num_ref[i] / den).astype(out_ref.dtype)


def _pa_pallas(q, k_pages, v_pages, block_tables, positions, block_r,
               block_kv, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, nh, dh = q.shape
    T = k_pages.shape[1]
    MB = block_tables.shape[1]
    n_blocks = MB // block_kv
    scale = dh ** -0.5

    def page_spec(i, j):
        # the indirection: this ref's page index comes from the scalar-
        # prefetched block table, so the pool rides in whole and only
        # the addressed page is pulled into VMEM per grid step
        return pl.BlockSpec(
            (1, T, nh, dh),
            lambda rb, b, tbl, ps, i=i, j=j:
                (tbl[rb * block_r + i, b * block_kv + j], 0, 0, 0))

    pairs = [(i, j) for i in range(block_r) for j in range(block_kv)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R // block_r, n_blocks),
        in_specs=[pl.BlockSpec((block_r, nh, dh),
                               lambda rb, b, tbl, ps: (rb, 0, 0))]
        + [page_spec(i, j) for i, j in pairs]
        + [page_spec(i, j) for i, j in pairs],
        out_specs=pl.BlockSpec((block_r, nh, dh),
                               lambda rb, b, tbl, ps: (rb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_r, nh, 1), jnp.float32),     # running max
            pltpu.VMEM((block_r, nh, dh), jnp.float32),    # numerator
            pltpu.VMEM((block_r, nh, 1), jnp.float32),     # denominator
        ],
    )
    nkv = block_r * block_kv
    fn = pl.pallas_call(
        functools.partial(_pa_kernel, block_r=block_r, block_kv=block_kv,
                          T=T, scale=scale, n_blocks=n_blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, nh, dh), q.dtype),
        interpret=interpret,
    )
    return fn(block_tables, positions.astype(jnp.int32), q,
              *([k_pages] * nkv), *([v_pages] * nkv))


def paged_attention_kwide(q, k_pages, v_pages, block_tables, positions,
                          config=None, interpret=None):
    """The speculative-verify face: ``K1`` query lanes per row against
    the SAME paged pool. ``q``: [R, K1, nh, dh] (lane i is the token fed
    at ``positions[r, i]``, K/V for all lanes already scattered);
    ``positions``: [R, K1] int32 — each lane masks its own columns, so
    lane i attends exactly the prefix a plain decode step at that
    position would. No new kernel: lanes flatten into rows
    ([R*K1, ...], tables repeated per lane) and ride the single-query
    face — the per-lane math is the decode step's verbatim, which is
    what makes greedy verification token-identical to non-speculative
    decode. ``config`` follows the decode contract: None (or a pick
    that cannot tile R*K1 rows) runs the gather reference.

    The gather path shares the K/V materialization across lanes: all
    K1 queries of a row walk the SAME block table, so the pool is
    gathered once per row ([R, C, ...]) and the lanes ride a batched
    [K1, C] attention against it — without the sharing, the verify
    step pays K1 duplicate gathers and K1 separate vector-matrix
    products, and the k-wide step costs ~K1x a plain decode step
    instead of ~1x gather + K1x (tiny) matmul FLOPs. The kernel path
    still flattens (the Pallas face is single-query per row); lanes
    repeat their tables and ride it unchanged."""
    R, K1, nh, dh = q.shape
    if resolve_block_config(config, R * K1, block_tables.shape[1]) is None:
        T = k_pages.shape[1]
        C = block_tables.shape[1] * T
        kc = k_pages[block_tables].reshape(R, C, nh, dh)
        vc = v_pages[block_tables].reshape(R, C, nh, dh)
        s = jnp.einsum("rlhd,rchd->rlhc", q, kc) * dh ** -0.5
        colmask = (jnp.arange(C, dtype=jnp.int32)[None, None, :]
                   <= positions.astype(jnp.int32)[:, :, None])
        s = jnp.where(colmask[:, :, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("rlhc,rchd->rlhd", p, vc)
    qf = q.reshape(R * K1, nh, dh)
    tables = jnp.repeat(block_tables, K1, axis=0)
    pos = positions.reshape(R * K1).astype(jnp.int32)
    out = paged_attention(qf, k_pages, v_pages, tables, pos,
                          config=config, interpret=interpret)
    return out.reshape(R, K1, nh, dh)


def paged_attention(q, k_pages, v_pages, block_tables, positions,
                    config=None, interpret=None):
    """One decode step of attention for the whole running batch.

    ``q``: [R, nh, dh] (the new token's query per row, K/V already
    scattered). ``k_pages``/``v_pages``: ONE layer's pool,
    [num_pages+1, T, nh, dh] (last page = trash). ``block_tables``:
    [R, max_blocks] int32, trash-padded. ``positions``: [R] int32 —
    columns <= position attend, the rest mask out. Returns [R, nh, dh].

    ``config`` is a paddle_tpu.tune "paged_attention" pick
    ({block_r, block_kv}); None or an invalid pick runs the gather
    reference instead (degrade, never fail)."""
    resolved = resolve_block_config(
        config if config is not None else DEFAULT_CONFIG,
        q.shape[0], block_tables.shape[1])
    if resolved is None:
        return paged_attention_reference(q, k_pages, v_pages,
                                         block_tables, positions)
    br, bkv = resolved
    if interpret is None:
        interpret = not on_tpu()
    return _pa_pallas(q, k_pages, v_pages, block_tables, positions,
                      br, bkv, interpret)
