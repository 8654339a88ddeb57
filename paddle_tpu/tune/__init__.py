"""Kernel autotuning: searched Pallas variants with a persistent
per-(device, shape) winner cache.

reference role: ``conv_cudnn_op.cu.cc`` — the reference answers a slow
generic op with a per-shape *algorithm search*; CUDA-L2 (PAPERS.md)
shows the same move beating cuBLAS with searched tilings. Here the
searchable things are Pallas kernel configs (tile/block shapes, grid
order) and the subsystem has four parts:

- **Search spaces** (``tune/space.py``): :class:`KernelSpace` declares
  the tunable parameters and validity constraints (VMEM footprint
  model, MXU/lane alignment) for conv3x3, flash_attention and matmul —
  the kernels in ``paddle_tpu/kernels/`` take these configs instead of
  hard-coded constants.
- **Autotune loop** (``tune/loop.py``): enumerate -> compile -> numeric
  parity vs stock XLA (an eligibility gate) -> time (wall clock on
  device, deterministic injectable timer on CPU) -> winner. Stock XLA
  is always in the race; per-candidate failures degrade-and-record at
  fault site ``tune.candidate``.
- **Winner cache** (``tune/cache.py``): JSON file keyed
  ``(device_kind, kernel, shape/dtype signature)`` at
  ``FLAGS.tune_cache_dir`` (beside the PR-3 compile cache), entry-CRC
  checked like checkpoints (fault site ``tune.cache``), fronted by a
  process-level in-memory layer.
- **Dispatch** (:func:`lookup`, wired into ops/nn_ops.py,
  ops/attention_ops.py, ops/math_ops.py): a cached winner activates
  the kernel with the winning config; a miss falls back to the
  kernel's default config where a kernel is already flag-enabled, and
  to stock XLA otherwise — training code never changes. Counters
  ``tune_hits`` / ``tune_misses`` / ``tune_fallbacks`` surface through
  ``Executor.stats`` and the profiler's ``tune`` timeline section.

Surface: ``paddle_tpu tune <config.py>`` (cli.py) tunes the kernels a
program actually uses; ``benchmark/mfu_ladder.py`` banks the
stock -> default-kernel -> tuned-kernel ladder per shape.
"""
from __future__ import annotations

import threading

from .cache import (WinnerCache, cache_key, clear_memory_cache,
                    default_cache_dir)
from .loop import TuneResult, XLA_CONFIG, autotune, default_timer
from .space import (Conv3x3Space, FlashAttentionSpace, KernelSpace,
                    MatmulSpace, PagedAttentionSpace, get_space,
                    signature, space_names)
from .timer import (model_timer, parity_ok, parity_report, table_timer,
                    time_best, wall_timer)

__all__ = [
    "KernelSpace", "Conv3x3Space", "FlashAttentionSpace", "MatmulSpace",
    "PagedAttentionSpace", "get_space", "space_names", "signature",
    "autotune", "TuneResult", "XLA_CONFIG", "default_timer",
    "WinnerCache", "cache_key", "default_cache_dir", "clear_memory_cache",
    "wall_timer", "model_timer", "table_timer", "time_best",
    "parity_ok", "parity_report",
    "lookup", "record_fallback", "counters", "reset_counters",
    "count_flash_blocks", "count_flash_kept", "count_moe_rungs",
    "count_gmm_blocks", "count_ssm_scan", "count_pick_sum",
]

# -- dispatch counters --------------------------------------------------------
# trace-time events (kernel dispatch happens while a program traces, once
# per compile — never per step), so a process-global tally is cheap and
# meaningful. Executor.run refreshes its stats dict from here; the
# profiler's `tune` timeline section mirrors it.

_counters_lock = threading.Lock()
_counters = {"tune_hits": 0, "tune_misses": 0, "tune_fallbacks": 0,
             "flash_kept_bytes": 0}
# {"fwd 256x256": launches traced}: the blocks each flash-attention kernel
# launch was built with (kernels/flash_attention.py: _blocks), whoever
# chose them — a cached winner or the kernel's own rule; grouped k/v heads
# and a window ride in the name ("fwd 512x512 g8 w2048")
_flash_blocks = {}
# the same names -> {"visited", "masked", "square"}: the score tiles one
# head's launch visits, those that carry a mask, and the whole square's
_flash_tiles = {}
# {"fwd 512x512": differentiated launches traced}: the flash-attention calls
# whose backward reads the o and lse their forward kept
# (kernels/flash_attention.py: _flash_fwd); _counters["flash_kept_bytes"]
# the bytes of those o and lse
_flash_kept = {}
# {"49152 -> 12288/24576/49152": launches traced}: the (token, pick) pairs
# of each expert layer traced and the static row counts its held part was
# compiled at (ops/decoder_ops.py: held_rungs); one rung: the layer holds
# every expert and has no conditional
_moe_rungs = {}
# {"gmm 256x2048x768 g16": launches traced}: the tiling (rows x contracted
# width x result width) each grouped-matmul launch was built with, by kernel
# (gmm, gmm_t, tgmm; kernels/grouped_matmul.py: default_tiling), and its
# number of groups
_gmm_blocks = {}
# {"Q128 x64 h64 g8 n128 p64 pallas": scans traced}: the shape of each
# state-space scan (kernels/ssd_scan.py: mixer_scan) and what runs it
_ssm_scans = {}
# {"16384x6x2560 rows98304 tile128": launches traced}: the shape of each
# held-pick sum (kernels/pick_sum.py: pick_sum), tokens x picks x width,
# the sorted rows it reads from and its token tile
_pick_sums = {}


def _bump(name):
    from .. import profiler
    with _counters_lock:
        _counters[name] += 1
    profiler.update_tune_counters(**{name: 1})


def _launch_name(kernel, block_q, block_k, group, window):
    name = "%s %dx%d" % (kernel, block_q, block_k)
    if group > 1:
        name += " g%d" % group
    if window is not None:
        name += " w%d" % window
    return name


def count_flash_blocks(kernel, block_q, block_k, group=1, window=None,
                       tiles=None):
    """One flash-attention launch (``kernel``: fwd / dq / dkv) traced at
    (block_q, block_k), ``group`` q heads to a k/v head, under ``window``;
    ``tiles``: what the launch visits (``flash_attention.tile_counts``)."""
    name = _launch_name(kernel, block_q, block_k, group, window)
    with _counters_lock:
        _flash_blocks[name] = _flash_blocks.get(name, 0) + 1
        if tiles is not None:
            _flash_tiles[name] = dict(tiles)


def count_flash_kept(block_q, block_k, group, window, nbytes):
    """One differentiated flash-attention call traced whose forward
    launch (at block_q x block_k) kept ``nbytes`` of o and lse for its
    backward."""
    name = _launch_name("fwd", block_q, block_k, group, window)
    with _counters_lock:
        _flash_kept[name] = _flash_kept.get(name, 0) + 1
        _counters["flash_kept_bytes"] += int(nbytes)


def count_moe_rungs(pairs, rungs):
    """One expert layer traced over ``pairs`` (token, pick) pairs with its
    held part compiled at the row counts ``rungs``."""
    name = "%d -> %s" % (pairs, "/".join("%d" % r for r in rungs))
    with _counters_lock:
        _moe_rungs[name] = _moe_rungs.get(name, 0) + 1


def count_gmm_blocks(kernel, tm, tk, tn, groups):
    """One grouped-matmul launch (``kernel``: gmm / gmm_t / tgmm) traced at
    tiling (tm, tk, tn) over ``groups`` groups."""
    name = "%s %dx%dx%d g%d" % (kernel, tm, tk, tn, groups)
    with _counters_lock:
        _gmm_blocks[name] = _gmm_blocks.get(name, 0) + 1


def count_ssm_scan(chunk, chunks, heads, groups, state, head_dim,
                   implementation):
    """One state-space scan traced (``kernels/ssd_scan.py: mixer_scan``):
    chunks of ``chunk`` positions, ``heads`` heads of ``head_dim`` reading
    ``groups`` B/C groups of ``state``, run by ``implementation``."""
    name = "Q%d x%d h%d g%d n%d p%d %s" % (chunk, chunks, heads, groups,
                                            state, head_dim, implementation)
    with _counters_lock:
        _ssm_scans[name] = _ssm_scans.get(name, 0) + 1


def count_pick_sum(T, k, d, rows, tile):
    """One held-pick sum traced over ``T`` tokens of ``k`` picks, rows of
    ``d``, from ``rows`` sorted rows, in token tiles of ``tile``."""
    name = "%dx%dx%d rows%d tile%d" % (T, k, d, rows, tile)
    with _counters_lock:
        _pick_sums[name] = _pick_sums.get(name, 0) + 1


def counters():
    """Snapshot of the process-level dispatch counters; ``flash_blocks``
    is the {"<kernel> <block_q>x<block_k>[ g<group>][ w<window>]":
    launches} tally, ``flash_tiles`` each name's {"visited", "masked",
    "square"} score tiles a head (of the launch traced last under it),
    ``flash_kept`` the tally of the differentiated calls by their forward
    launch's name, whose backward reads the o and lse that launch kept
    (``flash_kept_bytes`` of them in all), and ``moe_rungs`` the
    {"<pairs> -> <rung>/<rung>/...": launches} tally of the expert layers
    traced, ``gmm_blocks`` the {"<kernel> <tm>x<tk>x<tn> g<groups>":
    launches} tally of the grouped-matmul launches, ``ssm_scans`` the
    {"Q<chunk> x<chunks> h<heads> g<groups> n<state> p<head width>
    <implementation>": scans} tally of the state-space scans, ``pick_sums``
    the {"<T>x<k>x<d> rows<rows> tile<tile>": launches} tally of the
    held-pick sums."""
    with _counters_lock:
        return dict(_counters, flash_blocks=dict(_flash_blocks),
                    flash_tiles={k: dict(v)
                                 for k, v in _flash_tiles.items()},
                    flash_kept=dict(_flash_kept),
                    moe_rungs=dict(_moe_rungs),
                    gmm_blocks=dict(_gmm_blocks),
                    ssm_scans=dict(_ssm_scans),
                    pick_sums=dict(_pick_sums))


def reset_counters():
    from .. import profiler
    with _counters_lock:
        for k in _counters:
            _counters[k] = 0
        _flash_blocks.clear()
        _flash_tiles.clear()
        _flash_kept.clear()
        _moe_rungs.clear()
        _gmm_blocks.clear()
        _ssm_scans.clear()
        _pick_sums.clear()
    profiler.reset_tune_counters()


# -- dispatch ----------------------------------------------------------------

def _device_kind_cached():
    # device identity is stable for the process; avoid re-deriving it on
    # every traced dispatch
    global _DEVICE_KIND
    try:
        return _DEVICE_KIND
    except NameError:
        from .results import device_kind
        _DEVICE_KIND = device_kind()
        return _DEVICE_KIND


def lookup(kernel, key, enabled=False):
    """Kernel-dispatch decision for one call site.

    ``key`` is the shape key dict (see tune/space.py); ``enabled`` says
    whether the call site's legacy flag (conv_impl=pallas3x3,
    lstm_impl=pallas, ...) already opts this kernel in.

    Returns the config dict to run the kernel with, or ``None`` meaning
    *lower through stock XLA*:

    - cached winner for (device, kernel, sig)  -> that config
      (``tune_hits``; a winner of ``{"use": "xla"}`` means the search
      decided stock XLA is fastest — returns None but still a hit);
    - no winner, site flag-enabled             -> ``{}`` = the kernel's
      default config (``tune_misses``);
    - no winner, not enabled (or FLAGS.tune=0) -> ``None``
      (``tune_fallbacks``).

    Never raises: a corrupt/unreadable cache behaves as all-miss (the
    cache layer records the corruption event).
    """
    from ..flags import FLAGS
    if FLAGS.tune:
        try:
            cfg = WinnerCache().get_config(
                cache_key(_device_kind_cached(), kernel, signature(key)))
        except Exception:
            cfg = None  # cache trouble must never kill a trace
        if cfg is not None:
            _bump("tune_hits")
            if cfg.get("use") == "xla":
                return None
            return cfg
    if enabled:
        _bump("tune_misses")
        return {}
    _bump("tune_fallbacks")
    return None


def record_fallback(kernel):
    """Count a tunable call site where no kernel applies (shape outside
    the kernel's supported population) — it lowers through stock XLA."""
    del kernel  # per-kernel split not tracked yet; one gauge suffices
    _bump("tune_fallbacks")
