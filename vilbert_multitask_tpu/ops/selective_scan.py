"""The selective state-space scan (Mamba-1, arXiv:2312.00752): the
recurrence of a Mamba mixer, as one-token step, as chunked scan in
``jax.numpy`` and as a Pallas kernel on the TPU.

Per channel ``i`` of ``d_inner`` and state dimension ``n`` of ``d_state``,
token ``t``::

    h_t[n, i] = exp(dt_t[i] A[n, i]) h_{t-1}[n, i] + dt_t[i] c_t[i] B_t[n]
    m_t[i]    = sum_n h_t[n, i] C_t[n] + D[i] c_t[i]

``c_t`` is the channel's input (after the convolution and SiLU), ``dt_t > 0``
its step, ``B_t`` and ``C_t`` the token's input and output projections,
``A < 0`` the decay's rate: a decay per channel, state dimension *and
token*, which no matrix product carries (``ops/gated_delta.py``'s state
decays by one scalar a head). Everything is float32. The state is kept
``[d_state, d_inner]``, channels last: they are the lanes of every array
here and in HBM, where ``[d_inner, 16]`` would be padded eightfold.

**One token** (:func:`selective_step`, decode): the two lines above for any
leading batch.

**A chunk of T tokens** (prefill). :func:`selective_scan_jnp` walks the
tokens 64 at a time: inside a stretch the pairs ``(exp(dt_t A), dt_t c_t
B_t)`` are combined by an associative scan, ``(a, b) o (a', b') = (a a',
a' b + b')``, and the state is carried from stretch to stretch (the CPU's
path and the kernel's oracle). :func:`selective_scan` is the TPU kernel:
grid ``(channel blocks, token blocks)``, a block of 1,024 channels laid as
one ``[8, 128]`` vreg a state dimension, so the block's whole state is 16
vregs that stay in registers while the block's tokens are walked one by
one; a token's ``dt`` and ``c`` are one vreg each, its ``B_t[n]`` and
``C_t[n]`` scalars read from SMEM (where the whole chunk's lie, 256 KB for
2,048 tokens), and nothing is broadcast across lanes.
The state is read from HBM once and written once a call.

Tokens beyond a sequence's real length are given ``dt = 0`` by the caller:
``exp(0) = 1`` and ``0 c B = 0``, so the state that leaves is the state
after the last real token (their ``m`` is not read).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tokens combined by one associative scan in the ``jax.numpy`` form.
CHUNK = 64
# Tokens whose ``c``, ``dt`` and ``m`` one grid step of the kernel holds.
_TOKEN_BLOCK = 256
# Rows of 128 channels a grid step of the kernel holds: a vreg's sublanes.
_CHANNEL_ROWS = 8
_LANES = 128
# Tokens walked in one unrolled stretch of the kernel's loop.
_UNROLL = 2


def selective_step(h, c, dt, B, C, A, D):
    """One token for any leading batch. ``h`` [..., N, Ci] float32; ``c``,
    ``dt`` [..., Ci]; ``B``, ``C`` [..., N]; ``A`` [N, Ci]; ``D`` [Ci].
    Returns ``(m [..., Ci], h')``."""
    h = (jnp.exp(dt[..., None, :] * A) * h
         + (dt * c)[..., None, :] * B[..., :, None])
    return jnp.sum(h * C[..., :, None], axis=-2) + D * c, h


def selective_scan_jnp(c, dt, B, C, A, D, h0, *, chunk: int = CHUNK):
    """The recurrence over ``T`` tokens of one sequence, ``chunk`` at a
    time. ``c``, ``dt`` [T, Ci]; ``B``, ``C`` [T, N]; ``A`` [N, Ci]; ``D``
    [Ci]; ``h0`` [N, Ci] (all float32). Returns ``(m [T, Ci], h_T)``."""
    T = c.shape[0]
    L = math.gcd(T, chunk)

    def combine(left, right):
        (a, b), (a2, b2) = left, right
        return a * a2, a2 * b + b2

    def stretch(h, xs):
        c_, dt_, B_, C_ = xs
        decay = jnp.exp(dt_[:, None, :] * A)                 # [L, N, Ci]
        fed = (dt_ * c_)[:, None, :] * B_[:, :, None]
        kept, added = jax.lax.associative_scan(combine, (decay, fed))
        hs = kept * h + added
        return hs[-1], jnp.sum(hs * C_[:, :, None], axis=1) + D * c_

    def by_stretch(x):
        return x.reshape(T // L, L, *x.shape[1:])

    h, m = jax.lax.scan(stretch, h0.astype(jnp.float32),
                        tuple(by_stretch(x.astype(jnp.float32))
                              for x in (c, dt, B, C)))
    return m.reshape(T, -1), h


def _kernel(bc_ref, c_ref, dt_ref, a_ref, d_ref, h0_ref, m_ref, h_ref, *,
            states: int, unroll: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[...] = h0_ref[...]

    a = [a_ref[n] for n in range(states)]
    d = d_ref[...]
    first = pl.program_id(1) * c_ref.shape[0]   # the block's first token

    def token(t, hs):
        dt, c = dt_ref[t], c_ref[t]                          # [rows, 128]
        fed, m = dt * c, d * c
        mine = (first + t) * 2 * states         # ``B_t`` then ``C_t``
        out = []
        for n in range(states):
            h = jnp.exp(dt * a[n]) * hs[n] + fed * bc_ref[mine + n]
            m = m + h * bc_ref[mine + states + n]
            out.append(h)
        m_ref[t] = m
        return tuple(out)

    def some_tokens(i, hs):
        for u in range(unroll):
            hs = token(i * unroll + u, hs)
        return hs

    hs = jax.lax.fori_loop(0, c_ref.shape[0] // unroll, some_tokens,
                           tuple(h_ref[n] for n in range(states)))
    for n in range(states):
        h_ref[n] = hs[n]


def selective_scan(c, dt, B, C, A, D, h0, *, interpret: bool = False):
    """:func:`selective_scan_jnp` as one kernel call (``d_inner`` a multiple
    of 128; the profile's ``selective_scan`` custom call, whose first result
    ``f32[T, rows, 128]`` carries the chunk's tokens and channels)."""
    T, Ci = c.shape
    N = A.shape[0]
    if Ci % _LANES:
        raise ValueError(f"{Ci} channels are no multiple of {_LANES}")
    rows = Ci // _LANES
    R = _CHANNEL_ROWS if rows % _CHANNEL_ROWS == 0 else rows
    Tb = math.gcd(T, _TOKEN_BLOCK)

    def lanes(x):
        return x.astype(jnp.float32).reshape(*x.shape[:-1], rows, _LANES)

    per_token = pl.BlockSpec((Tb, R, _LANES), lambda i, j: (j, i, 0))
    per_state = pl.BlockSpec((N, R, _LANES), lambda i, j: (0, i, 0))
    bc = jnp.concatenate([B, C], axis=1).astype(jnp.float32).reshape(-1)
    m, h = pl.pallas_call(
        functools.partial(_kernel, states=N, unroll=math.gcd(Tb, _UNROLL)),
        grid=(rows // R, T // Tb),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  per_token, per_token, per_state,
                  pl.BlockSpec((R, _LANES), lambda i, j: (i, 0)), per_state],
        out_specs=[per_token, per_state],
        out_shape=[jax.ShapeDtypeStruct((T, rows, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((N, rows, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="selective_scan",
        interpret=interpret,
    )(bc, lanes(c), lanes(dt), lanes(A), lanes(D), lanes(h0))
    return m.reshape(T, Ci), h.reshape(N, Ci)
