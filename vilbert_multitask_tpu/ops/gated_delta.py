"""The gated delta rule (Gated DeltaNet): the recurrent mixer of a linear-
attention layer, as one-token step, as chunked scan in ``jax.numpy`` and as
a Pallas kernel on the TPU.

Per head, with key width ``dk`` and value width ``dv``, token ``t``::

    S_t = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t) k_t^T        o_t = S_t q_t

``a_t`` in (0, 1] is the decay gate (handed over as its logarithm ``g_t <=
0``), ``b_t`` in (0, 2) the write strength (above 1 the transition has a
negative eigenvalue), ``k_t`` has unit length, ``q_t`` is already scaled.
The state is kept transposed, ``St = S^T`` of shape ``[dk, dv]``, in
float32, so that every product below is a plain matmul.

**One token** (:func:`recurrent_step`, decode): ``u = b (v - a St^T k)``,
``St' = a St + k u^T``, ``o = St'^T q``: a rank-1 update per head.

**A chunk of C tokens** (prefill). With ``G_t`` the decay accumulated inside
the chunk and ``u_t`` the value actually written at ``t``::

    u_t = b_t (v_t - G_t S_0 k_t - sum_{i<t} (G_t/G_i) (k_i.k_t) u_i)

which is the unit lower triangular system ``(I + diag(b) A) U = diag(b) (V -
diag(G) K St_0)`` with ``A_ti = (G_t/G_i) k_t.k_i`` for ``i < t``. Its
solution splits into a part that does not know the incoming state and one
that is linear in it: ``U = u - w St_0`` with ``[u | w] = (I + diag(b)
A)^-1 diag(b) [V | diag(G) K]``. Then ``O = diag(G) Q St_0 + P U`` with
``P_ti = (G_t/G_i) q_t.k_i`` for ``i <= t``, and ``St_C = G_C St_0 +
(diag(G_C/G) K)^T U``.

So the work is two stages. :func:`chunk_prepare` computes ``u, w, diag(G)Q,
P, (diag(G_C/G)K)^T`` and ``G_C`` for every chunk at once, in plain XLA
and batched matmuls alone: :func:`unit_lower_inverse` builds the system's
inverse (blocks of 8 inverted exactly, then merged by doubling), and ``u``
and ``w`` are one product with it each (``lax.linalg.triangular_solve``
walked each 64 x 64 block row by row on the TPU, a tenth of a long-prompt
cell's chip). The *scan* carries the state through the chunks, three
matmuls and one rank-C update each: :func:`chunk_scan_jnp` is a
``lax.scan`` (CPU, tests), and :func:`gated_delta_scan` the TPU kernel:
grid ``(heads, chunks)``, the ``[dk, dv]`` float32 state of one head in
VMEM scratch across the chunk axis, read from HBM once and written once a
call.

Tokens beyond a sequence's real length are given ``g = 0, b = 0`` by the
caller: they write nothing and decay nothing, so the state that leaves is
the state after the last real token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
_HI = jax.lax.Precision.HIGHEST


def recurrent_step(state, q, k, v, g, beta):
    """One token for any leading batch of heads. ``state`` [..., dk, dv]
    float32; ``q, k`` [..., dk]; ``v`` [..., dv]; ``g, beta`` [...].
    Returns (o [..., dv] float32, new state)."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    a = jnp.exp(g.astype(jnp.float32))[..., None, None]
    decayed = a * state
    read = jnp.einsum("...kv,...k->...v", decayed, k, precision=_HI)
    u = beta.astype(jnp.float32)[..., None] * (v - read)
    new = decayed + k[..., :, None] * u[..., None, :]
    return jnp.einsum("...kv,...k->...v", new, q, precision=_HI), new


def gated_delta_recurrent(q, k, v, g, beta, state):
    """The recurrence one token at a time: the definition the chunked forms
    are tested against. ``q, k`` [T, H, dk]; ``v`` [T, H, dv]; ``g, beta``
    [T, H]; ``state`` [H, dk, dv]. Returns (o [T, H, dv], last state)."""
    def step(s, x):
        o, s = recurrent_step(s, *x)
        return s, o

    state, o = jax.lax.scan(step, state.astype(jnp.float32),
                            (q, k, v, g, beta))
    return o, state


def unit_lower_inverse(system):
    """The inverse of a batch of unit lower triangular ``[..., C, C]``
    float32 matrices, ``C = 8 * 2**j``, in batched matmuls alone.

    The diagonal blocks of 8 first: with ``N`` a block's strictly lower part
    (``N**8 = 0``), ``(I + N)^-1 = (I - N)(I + N**2)(I + N**4)``. Then blocks
    of 2b from blocks of b, ``[[A, 0], [E, D]]^-1 = [[A^-1, 0], [-D^-1 E
    A^-1, D^-1]]``, which over the whole matrix is ``X - X E_b X`` with ``X``
    the block-diagonal inverse so far and ``E_b`` the system's lower-left
    blocks of b inside the diagonal blocks of 2b. Every product is of whole
    ``C x C`` matrices, kept block-diagonal by zeros, at ``HIGHEST``. (A
    Neumann product over the whole matrix is no substitute: with write
    strengths near 2 the powers of ``N`` grow without bound.)"""
    C = system.shape[-1]
    blocks = C // 8
    assert C % 8 == 0 and blocks & (blocks - 1) == 0, C
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = jnp.eye(C, dtype=jnp.float32)

    def mm(a, b):
        return jnp.matmul(a, b, precision=_HI)

    def within(b):
        return row // b == col // b

    n = jnp.where(within(8) & (row > col), system, 0.0)
    n2 = mm(n, n)
    inv = mm(eye - n + n2 - mm(n, n2), eye + mm(n2, n2))
    b = 8
    while b < C:
        lower_left = jnp.where(within(2 * b) & ~within(b), system, 0.0)
        inv = inv - mm(mm(inv, lower_left), inv)
        b *= 2
    return inv


def chunk_prepare(q, k, v, g, beta, chunk: int = CHUNK) -> dict:
    """Stage one, every chunk at once. Inputs as
    :func:`gated_delta_recurrent` with T a multiple of ``chunk``. Returns
    float32 arrays laid out ``[H, N, ...]`` (N chunks): ``u`` [C, dv], ``w``
    [C, dk], ``qg`` [C, dk], ``p`` [C, C], ``kdt`` [dk, C], ``gc`` [1, 1]."""
    T, H, dk = q.shape
    n = T // chunk

    def heads_first(x):
        x = jnp.moveaxis(x.astype(jnp.float32), 1, 0)
        return x.reshape(H, n, chunk, *x.shape[2:])

    q, k, v, g, beta = map(heads_first, (q, k, v, g, beta))
    gsum = jnp.cumsum(g, axis=-1)                       # log G_t
    diff = gsum[..., :, None] - gsum[..., None, :]      # log G_t/G_i
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # The exponent is masked before exp: above the diagonal it is positive
    # and may overflow.
    upto = jnp.exp(jnp.where(row >= col, diff, -jnp.inf))
    before = jnp.where(row > col, upto, 0.0)
    kk = jnp.einsum("hntk,hnik->hnti", k, k, precision=_HI)
    qk = jnp.einsum("hntk,hnik->hnti", q, k, precision=_HI)
    decay = jnp.exp(gsum)[..., None]
    b = beta[..., None]
    inverse = unit_lower_inverse(jnp.eye(chunk, dtype=jnp.float32)
                                 + b * (kk * before))
    tail = jnp.exp(gsum[..., -1:] - gsum)[..., None]    # G_C/G_t
    return {"u": jnp.matmul(inverse, b * v, precision=_HI),
            "w": jnp.matmul(inverse, b * decay * k, precision=_HI),
            "qg": decay * q, "p": qk * upto,
            "kdt": jnp.swapaxes(tail * k, -1, -2),
            "gc": jnp.exp(gsum[..., -1])[..., None, None]}


def _chunk_update(state, u, w, qg, p, kdt, gc):
    """One chunk of stage two on one head; every product a plain matmul."""
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=_HI)
    written = u - dot(w, state)
    out = dot(qg, state) + dot(p, written)
    return out, gc * state + dot(kdt, written)


def chunk_scan_jnp(prep: dict, state):
    """Stage two as a ``lax.scan`` over chunks, all heads at once.
    Returns (o [H, N, C, dv] float32, last state [H, dk, dv])."""
    def step(s, x):
        o, s = jax.vmap(_chunk_update)(s, x["u"], x["w"], x["qg"], x["p"],
                                       x["kdt"], x["gc"])
        return s, o

    xs = {name: jnp.moveaxis(a, 1, 0) for name, a in prep.items()}
    state, o = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), state


def _scan_kernel(u_ref, w_ref, qg_ref, p_ref, kdt_ref, gc_ref, s0_ref,
                 o_ref, s_out_ref, state):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        state[...] = s0_ref[0]

    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)
    s = state[...]
    written = u_ref[0, 0] - dot(w_ref[0, 0], s)
    o_ref[0, 0] = (dot(qg_ref[0, 0], s)
                   + dot(p_ref[0, 0], written)).astype(o_ref.dtype)
    state[...] = gc_ref[0, 0] * s + dot(kdt_ref[0, 0], written)

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        s_out_ref[0] = state[...]


def gated_delta_scan(u, w, qg, p, kdt, gc, state, *, interpret: bool = False):
    """Stage two as the TPU kernel (the profile's ``gated_delta_scan``
    custom call: result ``o [H, N, C, dv]`` and the last state ``[H, dk,
    dv]``). One head and one chunk a grid step; the head's state stays in
    VMEM scratch over the chunk axis."""
    H, N, C, dv = u.shape
    dk = w.shape[-1]

    def per_chunk(*tail):
        return pl.BlockSpec((1, 1, *tail), lambda h, n: (h, n, 0, 0))

    per_head = pl.BlockSpec((1, dk, dv), lambda h, n: (h, 0, 0))
    return pl.pallas_call(
        _scan_kernel,
        grid=(H, N),
        in_specs=[per_chunk(C, dv), per_chunk(C, dk), per_chunk(C, dk),
                  per_chunk(C, C), per_chunk(dk, C), per_chunk(1, 1),
                  per_head],
        out_specs=[per_chunk(C, dv), per_head],
        out_shape=[jax.ShapeDtypeStruct((H, N, C, dv), jnp.float32),
                   jax.ShapeDtypeStruct((H, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="gated_delta_scan",
        interpret=interpret,
    )(u, w, qg, p, kdt, gc, state.astype(jnp.float32))


def gated_delta_chunked(q, k, v, g, beta, state, *, chunk: int = CHUNK,
                        use_pallas: bool = False, interpret: bool = False):
    """The recurrence over T tokens (a multiple of ``chunk``) chunk by
    chunk. Same arguments and results as :func:`gated_delta_recurrent`."""
    T, H, _ = q.shape
    prep = chunk_prepare(q, k, v, g, beta, chunk)
    if use_pallas:
        o, state = gated_delta_scan(**prep, state=state, interpret=interpret)
    else:
        o, state = chunk_scan_jnp(prep, state)
    return jnp.moveaxis(o.reshape(H, T, -1), 0, 1), state
