"""The selective scan (``ops/selective_scan.py``, ISSUE 34): the Pallas
kernel (in the interpreter) and the chunked ``jax.numpy`` form against the
one-token recurrence, float32 on the CPU. What is left between them is
summation order: read at 2e-6 on outputs of unit spread; ``ATOL`` allows
2e-5. The kernel's compile for the chip at the served size is in
``tests/test_gated_delta.py``, beside the other kernels': one file describes
the chip."""

import jax.numpy as jnp
import numpy as np
import pytest

from vilbert_multitask_tpu.ops import selective_scan as ss

ATOL = 2e-5


def inputs(T, Ci=256, N=4, seed=0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                        (T, Ci))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (N, Ci)), jnp.float32)
    return dict(c=normal(T, Ci), dt=dt, B=normal(T, N), C=normal(T, N), A=A,
                D=normal(Ci), h0=normal(N, Ci))


def token_by_token(c, dt, B, C, A, D, h0):
    h, ms = h0, []
    for t in range(c.shape[0]):
        m, h = ss.selective_step(h, c[t], dt[t], B[t], C[t], A, D)
        ms.append(m)
    return jnp.stack(ms), h


FORMS = {"jnp": ss.selective_scan_jnp,
         "kernel": lambda *a: ss.selective_scan(*a, interpret=True)}


def run(form, x):
    return FORMS[form](x["c"], x["dt"], x["B"], x["C"], x["A"], x["D"],
                       x["h0"])


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("T,Ci", [(64, 128), (96, 256), (512, 1024)])
def test_scan_equals_the_one_token_recurrence(form, T, Ci):
    """(512, 1024) walks two token blocks of the kernel and a whole
    [8, 128] block of channels; (96, 256) a chunk that is no multiple of
    the ``jax.numpy`` form's 64."""
    x = inputs(T, Ci)
    want_m, want_h = token_by_token(**x)
    m, h = run(form, x)
    assert float(jnp.abs(m - want_m).max()) < ATOL
    assert float(jnp.abs(h - want_h).max()) < ATOL


@pytest.mark.parametrize("form", list(FORMS))
def test_padding_rows_leave_the_state_of_the_last_real_row(form):
    """Rows from 40 on are padding (``dt`` = 0): the state that leaves is
    the state after row 39, whatever the padding rows hold."""
    x = inputs(64)
    real = 40
    x["dt"] = x["dt"].at[real:].set(0.0)
    _, want_h = token_by_token(**{k: (v[:real] if k in ("c", "dt", "B", "C")
                                      else v) for k, v in x.items()})
    m, h = run(form, x)
    assert float(jnp.abs(h - want_h).max()) < ATOL


@pytest.mark.parametrize("form", list(FORMS))
def test_two_chunks_equal_one(form):
    x = inputs(128)
    m, h = run(form, x)

    def part(lo, hi, h0):
        return run(form, {**{k: x[k][lo:hi] for k in ("c", "dt", "B", "C")},
                          "A": x["A"], "D": x["D"], "h0": h0})

    m1, h1 = part(0, 64, x["h0"])
    m2, h2 = part(64, 128, h1)
    assert float(jnp.abs(jnp.concatenate([m1, m2]) - m).max()) < ATOL
    assert float(jnp.abs(h2 - h).max()) < ATOL


@pytest.mark.parametrize("form", list(FORMS))
def test_decode_step_after_prefill_equals_the_scan_one_token_further(form):
    x = inputs(65)
    first = {k: (v[:64] if k in ("c", "dt", "B", "C") else v)
             for k, v in x.items()}
    _, h = run(form, first)
    m, h = ss.selective_step(h, x["c"][64], x["dt"][64], x["B"][64],
                             x["C"][64], x["A"], x["D"])
    want_m, want_h = token_by_token(**x)
    assert float(jnp.abs(m - want_m[64]).max()) < ATOL
    assert float(jnp.abs(h - want_h).max()) < ATOL


def test_step_batches_over_leading_axes():
    """One token of three slots at once equals the three taken apart."""
    xs = [inputs(1, seed=s) for s in range(3)]

    def stack(k):
        return jnp.stack([x[k][0] if k != "h0" else x[k] for x in xs])

    m, h = ss.selective_step(stack("h0"), stack("c"), stack("dt"),
                             stack("B"), stack("C"), xs[0]["A"], xs[0]["D"])
    for b, x in enumerate(xs):
        want_m, want_h = ss.selective_step(
            x["h0"], x["c"][0], x["dt"][0], x["B"][0], x["C"][0],
            xs[0]["A"], xs[0]["D"])
        assert float(jnp.abs(m[b] - want_m).max()) < 1e-6
        assert float(jnp.abs(h[b] - want_h).max()) < 1e-6


def test_kernel_refuses_channels_that_are_no_multiple_of_a_vreg():
    x = inputs(64, Ci=96)
    with pytest.raises(ValueError, match="multiple of 128"):
        run("kernel", x)
