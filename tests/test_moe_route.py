"""``ops/moe.py``'s router in its three forms: the default (softmax, top
k, renormalised, scaled), which must stay the form the sparse-expert
decoder was served with, bit for bit; a selection bias, which moves the
choice and never the weight; top-1 left unrenormalised. And a choice no
chip holds ("no expert"), which the expert layer drops. CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vilbert_multitask_tpu.ops import moe


def route_before(logits, k, scale):
    """``route`` as it was before the bias and the unrenormalised form."""
    scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top, experts = jax.lax.top_k(scores, k)
    weights = scale * top / jnp.sum(top, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), weights


@pytest.mark.parametrize("T,E,k,scale", [(300, 256, 10, 2.5),
                                         (64, 16, 4, 2.5), (33, 17, 1, 1.0)])
def test_the_default_is_the_form_before_bit_for_bit(T, E, k, scale):
    """The published sparse-expert router's sizes (256 experts, 10 a
    token, scale 2.5), the tiny test model's, and a top-1 of 17: the same
    experts and the same float32 weights, bit for bit, eager and jitted."""
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(T), (T, E))
    pairs = ((moe.route, route_before),
             (jax.jit(moe.route, static_argnums=(1, 2)),
              jax.jit(route_before, static_argnums=(1, 2))))
    for new, old in pairs:
        e_new, w_new = new(logits, k, scale)
        e_old, w_old = old(logits, k, scale)
        np.testing.assert_array_equal(e_new, e_old)
        assert np.asarray(w_new).tobytes() == np.asarray(w_old).tobytes()


def test_the_bias_moves_the_choice_not_the_weight():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.asarray([0.0, 0.0, 5.0, 0.0])
    experts, weights = moe.route(logits, 1, bias=bias, renormalize=False)
    assert experts.tolist() == [[2]]
    p = np.exp([2.0, 1.0, 0.0, -1.0])
    # the weight is the softmax's of the logits alone, at the chosen id
    assert np.allclose(np.asarray(weights), [[p[2] / p.sum()]], atol=1e-7)
    # with the bias the same choice renormalised would read 1
    _, renormalised = moe.route(logits, 1, bias=bias)
    assert np.allclose(np.asarray(renormalised), 1.0)


def test_top_1_without_renormalising_is_the_softmax_score():
    logits = jax.random.normal(jax.random.PRNGKey(1), (50, 17))
    experts, weights = moe.route(logits, 1, renormalize=False)
    scores = jax.nn.softmax(logits, -1)
    np.testing.assert_array_equal(experts[:, 0], jnp.argmax(logits, -1))
    np.testing.assert_allclose(weights[:, 0], scores.max(-1), rtol=1e-6)
    assert float(weights.max()) < 1.0


@pytest.mark.parametrize("kernel", [False, True], ids=["oracle", "kernel"])
def test_an_id_no_chip_holds_adds_nothing(kernel):
    """Id ``E`` (the "no expert" choice, past the experts held) is dropped
    like an expert held elsewhere: its rows get exactly 0, and it is not
    counted as a pair computed here."""
    T, H, W, E = 24, 32, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (T, H))
    gate_up = jax.random.normal(ks[1], (E, H, 2 * W)) / 6
    down = jax.random.normal(ks[2], (E, W, H)) / 4
    experts = (jnp.arange(T) % (E + 1))[:, None].astype(jnp.int32)
    weights = jnp.full((T, 1), 0.5)
    if kernel:
        out, stats = moe.experts_forward(x, experts, weights, (0, E),
                                         gate_up, down, interpret=True)
    else:
        out, stats = moe.experts_oracle(x, experts, weights, (0, E),
                                        gate_up, down)
    none = np.asarray(experts[:, 0] == E)
    assert not np.asarray(out)[none].any()
    assert np.abs(np.asarray(out)[~none]).min() > 0
    assert int(stats[0]) == int((~none).sum())
