"""Kernel search spaces: what is tunable, what is valid, what is stock.

The reference answers a slow generic conv by *searching* — cuDNN's
per-shape algorithm search in ``conv_cudnn_op.cu.cc`` enumerates
algorithms, times each, and keeps the winner per shape. A
:class:`KernelSpace` is that idea made declarative for Pallas kernels:

- ``params``: the tunable axes (tile/block shapes, grid order) with
  their candidate values;
- ``is_valid``: the hard constraints — divisibility, MXU/lane alignment
  (last dim multiples of 128, sublane multiples of 8), and a VMEM
  footprint model (``vmem_bytes`` must fit the ~16 MB/core budget with
  double-buffering headroom);
- ``build``: config -> callable, the thing the autotune loop compiles,
  parity-checks against ``reference`` (the stock XLA lowering), and
  times;
- ``make_operands``: deterministic example inputs for a shape key.

A *key* is a plain dict describing one shape/dtype population instance
(e.g. ``{"n": 128, "h": 28, "w": 28, "c": 128, "o": 128, "dtype":
"bfloat16"}``); ``signature(key)`` renders it canonically for the
winner cache. Four spaces ship: conv3x3, flash_attention, matmul and
paged_attention (kernels/{conv3x3,flash_attention,matmul,
paged_attention}.py — each taking the config these spaces emit instead
of hard-coded constants).
"""
from __future__ import annotations

import itertools

import numpy as np

__all__ = ["KernelSpace", "Conv3x3Space", "FlashAttentionSpace",
           "MatmulSpace", "PagedAttentionSpace", "get_space",
           "space_names", "signature"]

# usable VMEM budget per core: ~16 MB hardware minus headroom for
# double buffering and the compiler's own scratch
VMEM_BUDGET = 12 * 1024 * 1024


def _itemsize(dtype):
    import jax.numpy as jnp
    return jnp.dtype(dtype).itemsize


def signature(key):
    """Canonical cache-signature string for a shape key dict."""
    return ",".join("%s=%s" % (k, key[k]) for k in sorted(key))


class KernelSpace(object):
    """Base: declares the contract; subclasses fill the kernel-specific
    parts. ``candidates`` is shared — cartesian product of ``params``
    filtered by ``is_valid``, default config first, deduplicated."""

    name = None
    params = {}
    # what ``vmem_bytes`` may reach; a space whose reckoning counts the
    # pipeline's buffers and the compiler's scratch itself states its own
    vmem_budget = VMEM_BUDGET

    # -- to be provided by subclasses ---------------------------------------
    def default_config(self, key):
        raise NotImplementedError

    def is_valid(self, config, key):
        raise NotImplementedError

    def vmem_bytes(self, config, key):
        raise NotImplementedError

    def build(self, config, key):
        """config -> callable(*operands) running the kernel variant."""
        raise NotImplementedError

    def reference(self, key):
        """callable(*operands) running the stock XLA lowering."""
        raise NotImplementedError

    def make_operands(self, key, seed=0):
        raise NotImplementedError

    # -- shared --------------------------------------------------------------
    def candidates(self, key, budget=None):
        """Valid configs for ``key``: the default config first, then the
        pruned cartesian product of ``params``. ``budget`` caps the list
        length — the default survives any positive cap, ``budget=0``
        means ZERO kernel candidates (the autotune loop maps a total
        budget of 1 here: stock XLA only), ``None`` is uncapped."""
        default = self.default_config(key)
        out, seen = [], set()
        for cfg in [default] + self._enumerate(key):
            frozen = tuple(sorted(cfg.items()))
            if frozen in seen:
                continue
            seen.add(frozen)
            if self.is_valid(cfg, key) \
                    and self.vmem_bytes(cfg, key) <= self.vmem_budget:
                out.append(dict(cfg))
        if budget is not None:
            out = out[:max(int(budget), 0)]
        return out

    def _enumerate(self, key):
        names = sorted(self.params)
        return [dict(zip(names, vals)) for vals in
                itertools.product(*(self.params[n] for n in names))]


# ---------------------------------------------------------------------------


class Conv3x3Space(KernelSpace):
    """Tiling space of kernels/conv3x3.py (3x3/s1/p1 NHWC conv).

    key: {n, h, w, c, o, dtype}. block_o=0 means the full output-channel
    extent; grid_order 'no' is weight-stationary (batch outer), 'on'
    activation-stationary (output-channel outer)."""

    name = "conv3x3"
    params = {
        "block_n": (1, 2, 4, 8),
        "block_o": (0, 128, 256),
        "grid_order": ("no", "on"),
    }

    def default_config(self, key):
        from ..kernels.conv3x3 import DEFAULT_CONFIG
        return dict(DEFAULT_CONFIG)

    def is_valid(self, config, key):
        bn, bo = int(config["block_n"]), int(config["block_o"])
        if bn < 1 or key["n"] % bn:
            return False
        bo = bo or key["o"]
        if key["o"] % bo:
            return False
        # lane alignment: a partial output-channel tile must still fill
        # the 128-wide lane axis
        if bo != key["o"] and bo % 128:
            return False
        return config.get("grid_order", "no") in ("no", "on")

    def vmem_bytes(self, config, key):
        it = _itemsize(key["dtype"])
        bn = int(config["block_n"])
        bo = int(config["block_o"]) or key["o"]
        h, w, c = key["h"], key["w"], key["c"]
        x_tile = bn * (h + 2) * (w + 2) * c * it
        w_tile = 9 * c * bo * it
        o_tile = bn * h * w * bo * it
        acc = h * w * bo * 4
        # in/out tiles double-buffer; the f32 accumulator does not
        return 2 * (x_tile + w_tile + o_tile) + acc

    def make_operands(self, key, seed=0):
        import jax.numpy as jnp
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(key["n"], key["h"], key["w"], key["c"]),
                        key["dtype"])
        w = jnp.asarray(rng.randn(3, 3, key["c"], key["o"]) * 0.1,
                        key["dtype"])
        return (x, w)

    def build(self, config, key):
        import jax
        from ..kernels.conv3x3 import conv3x3_s1_nhwc
        frozen = tuple(sorted(config.items()))

        @jax.jit
        def fn(x, w):
            return conv3x3_s1_nhwc(x, w, None, frozen)

        return fn

    def reference(self, key):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def fn(x, w):
            return jax.lax.conv_general_dilated(
                x, w, window_strides=(1, 1), padding=[(1, 1), (1, 1)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32).astype(x.dtype)

        return fn


class FlashAttentionSpace(KernelSpace):
    """Block space of kernels/flash_attention.py.

    key: {b, s, h, d, causal, dtype}, and ``dv`` where the v heads' width
    differs from ``d``, ``hkv`` where k and v have fewer heads than q (q
    head j reads k/v head j // (h / hkv)), ``window`` where a causal call
    sees only that many keys back. The kernels run a sequence at ``padded_len(s)``
    whatever the blocks, so a block has to divide that; the other
    constraints are alignment and what the three kernels keep in VMEM —
    the forward's and dQ's resident k/v and the dK/dV kernel's whole-
    sequence q, dO and statistics. That reckoning is the kernel's own
    (``flash_attention.vmem_bytes``: it counts both pipeline buffers and
    the f32 tiles itself, so it is held to Mosaic's limit, not to the
    shared ``VMEM_BUDGET``): a winner the backward would refuse cannot be
    crowned."""

    name = "flash_attention"
    params = {
        "block_q": (64, 128, 256, 512),
        "block_k": (64, 128, 256, 512),
    }

    @staticmethod
    def _call(key):
        """(padded length, D, Dv, dtype) of the call the key describes."""
        from ..kernels.flash_attention import padded_len
        return (padded_len(key["s"]), key["d"], key.get("dv", key["d"]),
                key["dtype"])

    @staticmethod
    def _group(key):
        return key["h"] // key.get("hkv", key["h"])

    def default_config(self, key):
        """What a tune-cache miss runs: the kernels' own rule for the key.
        A config is one pair for all three kernels, so where their picks
        differ (a backward kernel's is narrower where its VMEM is) it is
        the narrower on each side, which every kernel's reckoning grants."""
        from ..kernels.flash_attention import KERNELS, default_blocks
        s, d, dv, dtype = self._call(key)
        picks = [default_blocks(kernel, s, s, d, dv, dtype,
                                bool(key.get("causal", False)),
                                self._group(key), key.get("window"))
                 for kernel in KERNELS]
        return {"block_q": min(bq for bq, _ in picks),
                "block_k": min(bk for _, bk in picks)}

    @property
    def vmem_budget(self):
        from ..kernels.flash_attention import VMEM_LIMIT
        return VMEM_LIMIT

    def is_valid(self, config, key):
        bq, bk = int(config["block_q"]), int(config["block_k"])
        s = self._call(key)[0]
        # a block wider than the sequence clamps to it; one such size
        # (128) stands for them all
        if max(bq, bk) > max(s, 128):
            return False
        bq, bk = min(bq, s), min(bk, s)
        # q rides the sublane axis of the score tile, k the 128-lane axis
        if bq < s and bq % 8 or bk < s and bk % 128:
            return False
        return s % bq == 0 and s % bk == 0

    def vmem_bytes(self, config, key):
        from ..kernels.flash_attention import KERNELS, vmem_bytes
        s, d, dv, dtype = self._call(key)
        bq = min(int(config["block_q"]), s)
        bk = min(int(config["block_k"]), s)
        return max(vmem_bytes(kernel, bq, bk, s, s, d, dv, _itemsize(dtype),
                              self._group(key))
                   for kernel in KERNELS)

    def make_operands(self, key, seed=0):
        import jax.numpy as jnp
        rng = np.random.RandomState(seed)
        b, s, h, d = key["b"], key["s"], key["h"], key["d"]
        hkv = key.get("hkv", h)
        q = jnp.asarray(rng.randn(b, s, h, d), key["dtype"])
        k = jnp.asarray(rng.randn(b, s, hkv, d), key["dtype"])
        v = jnp.asarray(rng.randn(b, s, hkv, key.get("dv", d)),
                        key["dtype"])
        return (q, k, v)

    def build(self, config, key):
        import jax
        from ..kernels.flash_attention import flash_attention
        causal = bool(key.get("causal", False))
        cfg, window = dict(config), key.get("window")

        @jax.jit
        def fn(q, k, v):
            return flash_attention(q, k, v, causal=causal, config=cfg,
                                   window=window)

        return fn

    def reference(self, key):
        import jax
        from ..kernels.flash_attention import _dense_reference
        causal, window = bool(key.get("causal", False)), key.get("window")

        @jax.jit
        def fn(q, k, v):
            B, S, H, D = q.shape
            t = lambda a: a.transpose(0, 2, 1, 3).reshape(
                B * a.shape[2], S, a.shape[3])
            o = _dense_reference(t(q), t(k), t(v), causal, D ** -0.5, window)
            return o.reshape(B, H, S, v.shape[3]).transpose(0, 2, 1, 3)

        return fn


class MatmulSpace(KernelSpace):
    """Tile space of kernels/matmul.py (2-D gemm). key: {m, k, n, dtype};
    block 0 = full extent (the kernel default)."""

    name = "matmul"
    params = {
        "block_m": (0, 8, 64, 128, 256, 512),
        "block_n": (0, 128, 256, 512),
        "block_k": (0, 128, 256, 512),
    }

    def default_config(self, key):
        from ..kernels.matmul import DEFAULT_CONFIG
        return dict(DEFAULT_CONFIG)

    def is_valid(self, config, key):
        M, K, N = key["m"], key["k"], key["n"]
        bm = int(config["block_m"]) or M
        bn = int(config["block_n"]) or N
        bk = int(config["block_k"]) or K
        if M % bm or N % bn or K % bk:
            return False
        # MXU alignment: sublane multiple of 8, lane multiple of 128
        if bm % 8 or bn % 128 or bk % 128:
            return False
        return True

    def vmem_bytes(self, config, key):
        it = _itemsize(key["dtype"])
        M, K, N = key["m"], key["k"], key["n"]
        bm = int(config["block_m"]) or M
        bn = int(config["block_n"]) or N
        bk = int(config["block_k"]) or K
        return 2 * (bm * bk + bk * bn) * it + bm * bn * (it + 4)

    def make_operands(self, key, seed=0):
        import jax.numpy as jnp
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(key["m"], key["k"]), key["dtype"])
        w = jnp.asarray(rng.randn(key["k"], key["n"]) * 0.1, key["dtype"])
        return (x, w)

    def build(self, config, key):
        import jax
        from ..kernels.matmul import matmul
        frozen = tuple(sorted(config.items()))

        @jax.jit
        def fn(x, w):
            return matmul(x, w, None, frozen)

        return fn

    def reference(self, key):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def fn(x, w):
            acc = (jnp.float32 if x.dtype in (jnp.bfloat16,) else None)
            return jnp.matmul(x, w, preferred_element_type=acc).astype(
                x.dtype)

        return fn


class PagedAttentionSpace(KernelSpace):
    """Block space of kernels/paged_attention.py — the generation
    engine's decode-step attention over the paged KV pool.

    key: {r, mb, t, nh, dh, dtype} (max_running, max_blocks per row,
    page_tokens, heads, head dim — ``kernels.paged_attention.
    population_key`` is the one encoder). ``block_r`` rows and
    ``block_kv`` pages per row ride one grid step; each (row, page)
    pair is a separate resident page in VMEM, so validity is
    divisibility plus the MAX_PAGES_RESIDENT cap and the VMEM budget.
    Candidate 0 of the autotune loop is stock XLA — which for this
    space IS the block-table gather path the engine runs today."""

    name = "paged_attention"
    params = {
        "block_r": (1, 2, 4, 8),
        "block_kv": (1, 2, 4, 8),
    }

    def default_config(self, key):
        from ..kernels.paged_attention import DEFAULT_CONFIG
        return dict(DEFAULT_CONFIG)

    def is_valid(self, config, key):
        from ..kernels.paged_attention import resolve_block_config
        return resolve_block_config(config, key["r"], key["mb"]) \
            is not None

    def vmem_bytes(self, config, key):
        from ..kernels.paged_attention import resolve_block_config
        resolved = resolve_block_config(config, key["r"], key["mb"])
        if resolved is None:
            return VMEM_BUDGET + 1
        br, bkv = resolved
        it = _itemsize(key["dtype"])
        nh, dh, t = key["nh"], key["dh"], key["t"]
        q_tile = br * nh * dh * it
        kv = 2 * br * bkv * t * nh * dh * it   # resident k+v pages
        o_tile = br * nh * dh * it
        scratch = br * nh * 4 * 2 + br * nh * dh * 4
        # q/kv/out tiles double-buffer; the f32 scratch does not
        return 2 * (q_tile + kv + o_tile) + scratch

    def make_operands(self, key, seed=0):
        """A running batch mid-flight: ragged positions, one row parked
        entirely on the trash page, one first-token row — the shapes the
        parity gate must hold on."""
        import jax.numpy as jnp
        rng = np.random.RandomState(seed)
        R, MB, T = key["r"], key["mb"], key["t"]
        nh, dh = key["nh"], key["dh"]
        pages = max(2, min(R * MB, 4 * MB))
        trash = pages
        kp = jnp.asarray(rng.randn(pages + 1, T, nh, dh), key["dtype"])
        vp = jnp.asarray(rng.randn(pages + 1, T, nh, dh), key["dtype"])
        q = jnp.asarray(rng.randn(R, nh, dh), key["dtype"])
        tables = np.full((R, MB), trash, np.int32)
        positions = np.zeros((R,), np.int32)
        for r in range(R):
            if r == 0:
                continue                       # row 0: all-trash parked
            positions[r] = 0 if r == 1 else int(rng.randint(0, MB * T))
            used = positions[r] // T + 1
            tables[r, :used] = rng.randint(0, pages, used)
        return (q, kp, vp, jnp.asarray(tables), jnp.asarray(positions))

    def build(self, config, key):
        import jax
        from ..kernels.paged_attention import paged_attention
        cfg = dict(config)

        @jax.jit
        def fn(q, kp, vp, tables, positions):
            return paged_attention(q, kp, vp, tables, positions,
                                   config=cfg)

        return fn

    def reference(self, key):
        import jax
        from ..kernels.paged_attention import paged_attention_reference

        return jax.jit(paged_attention_reference)


_SPACES = {sp.name: sp for sp in
           (Conv3x3Space(), FlashAttentionSpace(), MatmulSpace(),
            PagedAttentionSpace())}


def get_space(name):
    if name not in _SPACES:
        raise KeyError("unknown kernel space %r (have: %s)"
                       % (name, ", ".join(sorted(_SPACES))))
    return _SPACES[name]


def space_names():
    return sorted(_SPACES)
