"""``ops/cca.py``: compressed convolutional attention's preparation of
queries and keys, the Pallas kernel (in the interpreter) against its
``jax.numpy`` form, and that form against the equations written out row by
row. CPU, a small width (4 query and 2 key heads of 16, half of a head
rotated), float32 maps so that the two forms differ by summation order
only.

Tolerance: the kernel and its oracle run the same float32 operations on the
same rows; what is left is the order of the products' sums, read at 1e-6 on
queries of norm 4; ``ATOL`` 1e-5 allows ten times that.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vilbert_multitask_tpu.ops import cca

H, HKV, D, ROT = 4, 2, 16, 8
C = (H + HKV) * D
ATOL = 1e-5


def weights(seed, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    w0 = 1.0 + 0.3 * jax.random.normal(k[0], (2, C))
    w1 = (jax.random.normal(k[1], (2, H + HKV, D, D)) / math.sqrt(D)
          ).astype(dtype)
    tau = 3.0 + jax.random.uniform(k[2], (HKV,))
    return w0, w1, tau


def dims():
    return dict(heads=H, kv_heads=HKV, rotary_dim=ROT)


prep_jnp = jax.jit(functools.partial(cca.cca_prep_jnp, **dims()))
prep_prefill = jax.jit(functools.partial(cca.cca_prep_prefill, **dims(),
                                         interpret=True))
prep_rows = jax.jit(functools.partial(cca.cca_prep_rows, **dims(),
                                      interpret=True))


def by_rows(c, positions, w0, w1, tau):
    """The module's equations one row at a time, in plain numpy (float64):
    no shifted arrays, no heads laid side by side."""
    c = np.asarray(c, np.float64)
    w0, w1 = np.asarray(w0, np.float64), np.asarray(w1, np.float64)
    T = c.shape[0]
    row = lambda t: c[t] if t >= 0 else np.zeros(C)
    a = lambda t: w0[0] * row(t) + w0[1] * row(t - 1) if t >= 0 \
        else np.zeros(C)
    inv = 1.0 / 1e4 ** (np.arange(0, ROT, 2) / ROT)
    q_out, k_out = np.zeros((T, H, D)), np.zeros((T, HKV, D))

    def rot(x, p):
        x = x.copy()
        ang = p * inv
        lo, hi = x[:ROT // 2].copy(), x[ROT // 2:ROT].copy()
        x[:ROT // 2] = lo * np.cos(ang) - hi * np.sin(ang)
        x[ROT // 2:ROT] = hi * np.cos(ang) + lo * np.sin(ang)
        return x

    for t in range(T):
        heads = [a(t)[g * D:(g + 1) * D] @ w1[0, g]
                 + a(t - 1)[g * D:(g + 1) * D] @ w1[1, g]
                 for g in range(H + HKV)]
        pre = [c[t][g * D:(g + 1) * D] for g in range(H + HKV)]
        for h in range(H):
            q = heads[h] + 0.5 * (pre[h] + pre[H + h // (H // HKV)])
            q = math.sqrt(D) * q / math.sqrt(q @ q + cca.EPS)
            q_out[t, h] = rot(q, positions[t])
        for g in range(HKV):
            mean = np.mean(pre[g * (H // HKV):(g + 1) * (H // HKV)], 0)
            k = heads[H + g] + 0.5 * (mean + pre[H + g])
            k = float(tau[g]) * k / math.sqrt(k @ k + cca.EPS)
            k_out[t, g] = rot(k, positions[t])
    return q_out.reshape(T, -1), k_out.reshape(T, -1)


def test_the_jnp_form_is_the_equations_row_by_row():
    """From a sequence's start (rows before it are 0), one chunk."""
    w0, w1, tau = weights(0)
    T = 24
    c = jax.random.normal(jax.random.PRNGKey(1), (T, C))
    pos = jnp.arange(T)
    cos, sin = cca.rotary_tables(pos, D, ROT, 1e4)
    q, k = prep_jnp(*cca.prefill_rows(jnp.zeros((2, C)), c), w0, w1, tau,
                    cos, sin)
    q_ref, k_ref = by_rows(c, np.arange(T), w0, w1, tau)
    np.testing.assert_allclose(q, q_ref, atol=ATOL)
    np.testing.assert_allclose(k, k_ref, atol=ATOL)


@pytest.mark.parametrize("T,start", [(16, 0), (48, 32)],
                         ids=["sequence start", "after a chunk boundary"])
def test_prefill_kernel_equals_the_jnp_form(T, start):
    """The kernel over a chunk (its window: the carried rows in front, row
    blocks that overlap by the halo) against the oracle. At ``start`` 0 the
    carried rows are zeros; past a boundary they are the rows before."""
    w0, w1, tau = weights(2)
    whole = jax.random.normal(jax.random.PRNGKey(3), (start + T, C))
    tail = (whole[start - 2:start] if start else jnp.zeros((2, C)))
    c = whole[start:]
    cos, sin = cca.rotary_tables(start + jnp.arange(T), D, ROT, 1e4)
    want = prep_jnp(*cca.prefill_rows(tail, c), w0, w1, tau, cos, sin)
    got = prep_prefill(tail, c, w0, w1, tau, cos, sin)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=ATOL)
    # And the chunk equals the same rows of one pass from the start.
    cos0, sin0 = cca.rotary_tables(jnp.arange(start + T), D, ROT, 1e4)
    once = prep_jnp(*cca.prefill_rows(jnp.zeros((2, C)), whole), w0, w1,
                    tau, cos0, sin0)
    for a, b in zip(got, once):
        np.testing.assert_allclose(a, b[start:], atol=ATOL)


def test_rows_kernel_equals_the_jnp_form():
    """The decode form: each row with carried rows of its own."""
    w0, w1, tau = weights(4)
    B = 8
    cur, p1, p2 = jax.random.normal(jax.random.PRNGKey(5), (3, B, C))
    pos = jnp.array([0, 1, 7, 100, 3, 64, 2, 900])
    cos, sin = cca.rotary_tables(pos, D, ROT, 1e4)
    want = prep_jnp(cur, p1, p2, w0, w1, tau, cos, sin)
    got = prep_rows(cur, p1, p2, w0, w1, tau, cos, sin)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=ATOL)


def test_norms_are_sqrt_d_and_the_temperature():
    """The rotary embedding turns pairs: the query's norm stays sqrt(d) and
    the key's its head's temperature."""
    w0, w1, tau = weights(6)
    c = jax.random.normal(jax.random.PRNGKey(7), (8, C))
    cos, sin = cca.rotary_tables(jnp.arange(8) + 50, D, ROT, 1e4)
    q, k = prep_jnp(*cca.prefill_rows(jnp.zeros((2, C)), c), w0, w1, tau,
                    cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(q).reshape(8, H, D), axis=-1),
        math.sqrt(D), rtol=1e-5)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(k).reshape(8, HKV, D), axis=-1),
        np.broadcast_to(np.asarray(tau), (8, HKV)), rtol=1e-5)
