"""Compressed convolutional attention's preparation of queries and keys
(CCA, arXiv:2510.04476): what a ``zaya`` layer does between its query and
key projections and the attention over the pages, as a ``jax.numpy`` form
and as one Pallas kernel (``cca_prep`` in a profile).

The latent channels of a token are ``c_t = [q~_t ; k~_t]``: ``H`` query
heads and ``H_kv`` key heads of width ``d``, ``C = (H + H_kv) d`` channels,
float32. For each row::

    a_t   = w0[0] * c_t + w0[1] * c_{t-1}                (depthwise, 2 taps)
    b_t   = W1[0] a_t + W1[1] a_{t-1}                    (a d x d map a head)
    q_h   = b^q_h + (q~_h + k~_{g(h)}) / 2               g(h) = h // (H / H_kv)
    k_g   = b^k_g + (mean_{h: g(h) = g} q~_h + k~_g) / 2
    q^_h  = sqrt(d) q_h / |q_h|,   k^_g = tau_g k_g / |k_g|
    then the rotary embedding on the first ``r`` channels of every head,
    channel ``i < r / 2`` paired with ``i + r / 2`` (``rotate_half``).

``|x|`` is ``sqrt(sum x^2 + EPS)``. Rows before a sequence's start are 0.
So a row needs the two rows of ``c`` before it and nothing else of the
past: that pair is what a sequence carries from chunk to chunk and step to
step (its slot's ``cca_conv``).

Both forms take the rows as ``cur``, ``prev1``, ``prev2`` (``c_t``,
``c_{t-1}``, ``c_{t-2}``); :func:`prefill_rows` lays out a chunk's window
for them. The kernel has two entry points over one body: a chunk of one
sequence, whose grid walks blocks of rows of a *window* that holds the
carried rows in front of the chunk (each block is read once, with the 8
rows before it, and the shifted rows are taken from it in VMEM), and a
decode step, whose rows are of different sequences and come with their
own carried rows. The conv1 products take bfloat16 operands (the map's
dtype) and accumulate in float32; everything else is float32. Both return
``q^`` [N, H d] and ``k^`` [N, H_kv d] in the map's dtype.

:func:`cca_prep_jnp` is the CPU's path and the kernel's oracle.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EPS = 1e-6
# Rows a grid step of the prefill kernel prepares.
_ROW_BLOCK = 256
# Rows in front of a block that its window holds: the two carried rows,
# padded to a float32 tile's sublanes.
_HALO = 8
_VMEM_LIMIT = 64 * 1024 * 1024


def rotary_tables(positions, head_dim: int, rotary_dim: int, theta: float):
    """(cos, sin) [N, head_dim] float32 over a head's lanes: channel ``i <
    r`` at the frequency of its pair ``i mod (r / 2)``; beyond ``r`` cos 1
    and sin 0, so those channels pass."""
    inv = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                          / rotary_dim)
    angle = positions.astype(jnp.float32)[:, None] * inv[None, :]
    pad = head_dim - rotary_dim
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle),
                           jnp.ones((angle.shape[0], pad), jnp.float32)], -1)
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle),
                           jnp.zeros((angle.shape[0], pad), jnp.float32)], -1)
    return cos, sin


def _rotate(x, cos, sin, rotary_dim: int, ahead, behind):
    """The rotary embedding of rows ``x`` [R, d]: ``ahead`` / ``behind`` are
    ``x`` at channel ``i + r / 2`` / ``i - r / 2`` (modulo ``d``)."""
    half = rotary_dim // 2
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    pair = jnp.where(lane < half, -ahead, jnp.where(lane < rotary_dim,
                                                    behind, 0.0))
    return x * cos + pair * sin


def _prep(cur, prev1, prev2, w0, w1, tau, cos, sin, *, heads: int,
          kv_heads: int, rotary_dim: int, shift):
    """The body both forms share: rows [R, C] float32 -> (q^ [R, H d], k^
    [R, H_kv d]) float32. ``w0[tap]`` is [1, C] float32, ``w1[tap, group]``
    [d, d], ``tau[g]`` a scalar; ``shift(x, n)`` is ``x`` at channel ``i -
    n`` modulo the width."""
    d = cos.shape[-1]
    group = heads // kv_heads
    w_now, w_before = w0[0], w0[1]
    a0 = w_now * cur + w_before * prev1                 # a_t
    a1 = w_now * prev1 + w_before * prev2               # a_{t-1}

    def head(x, g):
        return x[:, g * d:(g + 1) * d]

    def conv1(g):
        return (jnp.dot(head(a0, g).astype(w1.dtype), w1[0, g],
                        preferred_element_type=jnp.float32)
                + jnp.dot(head(a1, g).astype(w1.dtype), w1[1, g],
                          preferred_element_type=jnp.float32))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + EPS)

    def rotary(x):
        return _rotate(x, cos, sin, rotary_dim, shift(x, d - rotary_dim // 2),
                       shift(x, rotary_dim // 2))

    qs, ks = [], []
    for g in range(kv_heads):
        k_pre = head(cur, heads + g)
        mine = [head(cur, h) for h in range(g * group, (g + 1) * group)]
        for h, q_pre in zip(range(g * group, (g + 1) * group), mine):
            q = conv1(h) + 0.5 * (q_pre + k_pre)
            qs.append(rotary(math.sqrt(d) * unit(q)))
        k = conv1(heads + g) + 0.5 * (sum(mine) / group + k_pre)
        ks.append(rotary(tau[g] * unit(k)))
    return qs, ks


def _jnp_shift(x, n):
    return jnp.roll(x, n, axis=-1)


def cca_prep_jnp(cur, prev1, prev2, w0, w1, tau, cos, sin, *, heads: int,
                 kv_heads: int, rotary_dim: int):
    """The module's equations over rows ``cur``, ``prev1``, ``prev2`` [N,
    C] float32 (``c_t``, ``c_{t-1}``, ``c_{t-2}``); ``w0`` [2, C] float32,
    ``w1`` [2, H + H_kv, d, d], ``tau`` [H_kv] float32, ``cos``, ``sin`` [N,
    d] (:func:`rotary_tables`). Returns (q^ [N, H d], k^ [N, H_kv d]) in
    ``w1``'s dtype."""
    qs, ks = _prep(cur, prev1, prev2, _taps(w0), w1,
                   tau.astype(jnp.float32), cos, sin, heads=heads,
                   kv_heads=kv_heads, rotary_dim=rotary_dim,
                   shift=_jnp_shift)
    return (jnp.concatenate(qs, -1).astype(w1.dtype),
            jnp.concatenate(ks, -1).astype(w1.dtype))


def _taps(w0):
    """``w0`` [2, C] as the body reads it: [2, 1, C] float32, a tap's row
    at the front of its own tile."""
    return w0.astype(jnp.float32)[:, None, :]


def prefill_rows(tail, c):
    """A chunk's rows as the forms take them: ``tail`` [2, C] (``c_{-2}``,
    ``c_{-1}``, carried) and the chunk's ``c`` [T, C] -> (cur, prev1,
    prev2) [T, C] each. The ``jax.numpy`` path's; the kernel reads the
    same from one window (:func:`cca_prep_prefill`)."""
    T = c.shape[0]
    window = jnp.concatenate([tail.astype(c.dtype), c], axis=0)
    return window[2:], window[1:T + 1], window[:T]


def _kernel_shift(x, n):
    # ``pltpu.roll`` moves lane i to i + n, as ``jnp.roll`` does.
    return pltpu.roll(x, n % x.shape[-1], x.ndim - 1)


def _store(q_ref, k_ref, qs, ks):
    d = qs[0].shape[-1]
    for h, q in enumerate(qs):
        q_ref[:, h * d:(h + 1) * d] = q.astype(q_ref.dtype)
    for g, k in enumerate(ks):
        k_ref[:, g * d:(g + 1) * d] = k.astype(k_ref.dtype)


def _window_kernel(win_ref, w0_ref, w1_ref, tau_ref, cos_ref, sin_ref,
                   q_ref, k_ref, *, rows: int, **dims):
    """One block of a chunk: ``win_ref`` [HALO + rows, C] holds the block's
    rows after the ``HALO`` rows before them."""
    qs, ks = _prep(win_ref[_HALO:_HALO + rows],
                   win_ref[_HALO - 1:_HALO - 1 + rows],
                   win_ref[_HALO - 2:_HALO - 2 + rows], w0_ref, w1_ref,
                   tau_ref, cos_ref[...], sin_ref[...],
                   shift=_kernel_shift, **dims)
    _store(q_ref, k_ref, qs, ks)


def _rows_kernel(cur_ref, prev1_ref, prev2_ref, w0_ref, w1_ref, tau_ref,
                 cos_ref, sin_ref, q_ref, k_ref, **dims):
    qs, ks = _prep(cur_ref[...], prev1_ref[...], prev2_ref[...], w0_ref,
                   w1_ref, tau_ref, cos_ref[...], sin_ref[...],
                   shift=_kernel_shift, **dims)
    _store(q_ref, k_ref, qs, ks)


def _whole(shape):
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _call(kernel, grid, row_specs, inputs, w0, w1, tau, cos, sin, rows_out,
          block, heads, kv_heads, rotary_dim, interpret):
    d = w1.shape[-1]
    dims = dict(heads=heads, kv_heads=kv_heads, rotary_dim=rotary_dim)
    lanes = pl.BlockSpec((block, d), lambda i: (i, 0))
    taps = _taps(w0)
    return pl.pallas_call(
        functools.partial(kernel, **dims),
        grid=grid,
        in_specs=[*row_specs, _whole(taps.shape), _whole(w1.shape),
                  pl.BlockSpec(memory_space=pltpu.SMEM), lanes, lanes],
        out_specs=[pl.BlockSpec((block, heads * d), lambda i: (i, 0)),
                   pl.BlockSpec((block, kv_heads * d), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows_out, heads * d), w1.dtype),
                   jax.ShapeDtypeStruct((rows_out, kv_heads * d), w1.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="cca_prep",
        interpret=interpret,
    )(*inputs, taps, w1, tau.astype(jnp.float32), cos, sin)


def cca_prep_prefill(tail, c, w0, w1, tau, cos, sin, *, heads: int,
                     kv_heads: int, rotary_dim: int,
                     interpret: bool = False):
    """The kernel over a chunk of one sequence: ``tail`` [2, C] the carried
    rows, ``c`` [T, C] float32, the rest as :func:`cca_prep_jnp`'s. The
    window is ``[HALO - 2 zero rows, tail, c]``; grid step ``i`` reads its
    rows ``i R .. i R + HALO + R`` (element offsets: the blocks overlap by
    ``HALO`` rows)."""
    T, C = c.shape
    block = math.gcd(T, _ROW_BLOCK)
    window = jnp.concatenate(
        [jnp.zeros((_HALO - 2, C), jnp.float32), tail.astype(jnp.float32),
         c.astype(jnp.float32)], axis=0)
    spec = pl.BlockSpec((pl.Element(_HALO + block), pl.Element(C)),
                        lambda i: (i * block, 0))
    return _call(functools.partial(_window_kernel, rows=block), (T // block,),
                 [spec], [window], w0, w1, tau, cos, sin, T, block, heads,
                 kv_heads, rotary_dim, interpret)


def cca_prep_rows(cur, prev1, prev2, w0, w1, tau, cos, sin, *, heads: int,
                  kv_heads: int, rotary_dim: int, interpret: bool = False):
    """The kernel over rows of different sequences (a decode step), each
    with its own carried rows ``prev1``, ``prev2`` [B, C]."""
    B, C = cur.shape
    rows = _whole((B, C))
    return _call(_rows_kernel, (1,), [rows, rows, rows],
                 [cur.astype(jnp.float32), prev1.astype(jnp.float32),
                  prev2.astype(jnp.float32)], w0, w1, tau, cos, sin, B, B,
                 heads, kv_heads, rotary_dim, interpret)
