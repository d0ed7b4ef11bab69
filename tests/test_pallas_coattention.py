"""Pallas flash co-attention vs the XLA reference path.

These tests run on CPU, so every call says ``interpret=True`` (or sets
``pallas_interpret`` on the module/config) — the explicit choice; nothing
infers it from the backend. They validate the exact blockwise online-softmax
math. The same kernel compiled by Mosaic (``interpret=False``, bf16, the
three serving geometries) is checked on the chip by ``chip_smoke.py``.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from vilbert_multitask_tpu.config import ViLBertConfig
from vilbert_multitask_tpu.models.vilbert import ViLBertForVLTasks
from vilbert_multitask_tpu.ops.attention import mask_to_bias, multi_head_attention
from vilbert_multitask_tpu.ops.coattention import flash_cross_attention


def _rand_qkv(rng, B, Nq, Nk, H, D):
    return (
        jnp.asarray(rng.normal(size=(B, Nq, H, D)), jnp.float32),
        jnp.asarray(rng.normal(size=(B, Nk, H, D)), jnp.float32),
        jnp.asarray(rng.normal(size=(B, Nk, H, D)), jnp.float32),
    )


def test_matches_xla_reference_serving_shapes():
    """38 text tokens × 101 regions — the exact serving geometry."""
    rng = np.random.default_rng(0)
    B, Nq, Nk, H, D = 2, 38, 101, 8, 128
    q, k, v = _rand_qkv(rng, B, Nq, Nk, H, D)
    mask = jnp.asarray(rng.random((B, Nk)) < 0.9, jnp.int32)
    mask = mask.at[:, 0].set(1)
    bias = mask_to_bias(mask)
    ref, _ = multi_head_attention(q, k, v, bias)
    out = flash_cross_attention(q, k, v, bias, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_blockwise_path_multiple_kv_blocks():
    """Nk spanning several KV tiles exercises the online-softmax recurrence."""
    rng = np.random.default_rng(1)
    B, Nq, Nk, H, D = 1, 16, 300, 2, 64
    q, k, v = _rand_qkv(rng, B, Nq, Nk, H, D)
    mask = jnp.ones((B, Nk), jnp.int32)
    bias = mask_to_bias(mask)
    ref, _ = multi_head_attention(q, k, v, bias)
    out = flash_cross_attention(q, k, v, bias, block_q=8, block_k=64,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_masked_keys_do_not_leak():
    """Fully-masked tail keys must not affect the context at all."""
    rng = np.random.default_rng(2)
    B, Nq, Nk, H, D = 1, 8, 40, 2, 32
    q, k, v = _rand_qkv(rng, B, Nq, Nk, H, D)
    mask = jnp.concatenate(
        [jnp.ones((B, 25), jnp.int32), jnp.zeros((B, 15), jnp.int32)], axis=1)
    out_full = flash_cross_attention(q, k, v, mask_to_bias(mask),
                                     interpret=True)
    # Same computation with garbage in the masked tail.
    k2 = k.at[:, 25:].set(1e3)
    v2 = v.at[:, 25:].set(-1e3)
    out_garbage = flash_cross_attention(q, k2, v2, mask_to_bias(mask),
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out_full), np.asarray(out_garbage),
                               atol=1e-5)


def test_model_parity_pallas_vs_xla(tiny_config, rng):
    """Full trunk forward: Pallas co-attention ≡ XLA co-attention."""
    cfg_x = tiny_config
    cfg_p = dataclasses.replace(cfg_x, use_pallas_coattention=True,
                                pallas_interpret=True)
    B, Nt, Nv = 2, 10, 7
    nrng = np.random.default_rng(3)
    args = (
        jnp.asarray(nrng.integers(0, cfg_x.vocab_size, (B, Nt)), jnp.int32),
        jnp.asarray(nrng.normal(size=(B, Nv, cfg_x.v_feature_size)),
                    jnp.float32),
        jnp.asarray(nrng.random((B, Nv, 5)), jnp.float32),
        jnp.zeros((B, Nt), jnp.int32),
        jnp.ones((B, Nt), jnp.int32),
        jnp.ones((B, Nv), jnp.int32),
        None,
        jnp.ones((B, 1), jnp.int32),
    )
    model_x = ViLBertForVLTasks(cfg_x, dtype=jnp.float32)
    model_p = ViLBertForVLTasks(cfg_p, dtype=jnp.float32)
    params = model_x.init(rng, *args, deterministic=True)["params"]
    out_x = model_x.apply({"params": params}, *args, deterministic=True)
    out_p = model_p.apply({"params": params}, *args, deterministic=True)
    np.testing.assert_allclose(np.asarray(out_p.vil_prediction),
                               np.asarray(out_x.vil_prediction),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(out_p.vision_logit),
                               np.asarray(out_x.vision_logit),
                               atol=1e-4, rtol=1e-4)


def test_self_attention_pallas_matches_xla(rng):
    """FusedSelfAttention kernel path (head_dim=128) ≡ XLA path."""
    import flax.linen as nn

    from vilbert_multitask_tpu.ops.attention import FusedSelfAttention

    nrng = np.random.default_rng(7)
    B, N, H = 2, 23, 256  # 2 heads × head_dim 128 → kernel-eligible
    x = jnp.asarray(nrng.normal(size=(B, N, H)), jnp.float32)
    mask = jnp.ones((B, N), jnp.int32).at[:, 17:].set(0)
    bias = mask_to_bias(mask)
    mod_x = FusedSelfAttention(hidden_size=H, num_heads=2, use_pallas=False)
    mod_p = FusedSelfAttention(hidden_size=H, num_heads=2, use_pallas=True,
                               pallas_interpret=True)
    params = mod_x.init(rng, x, bias)["params"]
    ref, probs = mod_x.apply({"params": params}, x, bias)
    out, none_probs = mod_p.apply({"params": params}, x, bias)
    assert none_probs is None and probs is not None
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_self_attention_kernel_at_visual_stream_geometry(rng):
    """The EXACT serving eligibility claim (config.py): the 1024-wide/8-head
    visual stream (head_dim 128) takes the kernel path at its real length
    (101 regions) and matches XLA; BERT-base text (768/12, head_dim 64)
    must NOT take it (a 64-lane op would waste half the MXU)."""
    from vilbert_multitask_tpu.ops.attention import FusedSelfAttention

    nrng = np.random.default_rng(11)
    B, N, H, heads = 2, 101, 1024, 8  # visual stream, serving geometry
    x = jnp.asarray(nrng.normal(size=(B, N, H)), jnp.float32)
    mask = jnp.ones((B, N), jnp.int32).at[:, 77:].set(0)
    bias = mask_to_bias(mask)
    mod_x = FusedSelfAttention(hidden_size=H, num_heads=heads,
                               use_pallas=False)
    mod_p = FusedSelfAttention(hidden_size=H, num_heads=heads,
                               use_pallas=True, pallas_interpret=True)
    params = mod_x.init(rng, x, bias)["params"]
    ref, _ = mod_x.apply({"params": params}, x, bias)
    out, probs = mod_p.apply({"params": params}, x, bias)
    assert probs is None  # proof the kernel path actually ran
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    # Text-stream geometry: head_dim 64 → kernel ineligible, probs returned.
    Ht, ht_heads, Nt = 768, 12, 38
    xt = jnp.asarray(nrng.normal(size=(1, Nt, Ht)), jnp.float32)
    bt = mask_to_bias(jnp.ones((1, Nt), jnp.int32))
    mod_t = FusedSelfAttention(hidden_size=Ht, num_heads=ht_heads,
                               use_pallas=True)
    pt = mod_t.init(rng, xt, bt)["params"]
    _, probs_t = mod_t.apply({"params": pt}, xt, bt)
    assert probs_t is not None  # stayed on XLA as designed


def test_kernel_under_a_mesh_runs_through_shard_map():
    """A partitioned program cannot contain a bare Mosaic call (XLA refuses
    to partition it), so under a mesh the kernel runs through shard_map:
    rows over dp and heads over tp where the axes divide them — the rule
    parallel/sharding.py places batches by — replicated otherwise."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    rng = np.random.default_rng(5)
    sharded_kernel = jax.jit(functools.partial(
        flash_cross_attention, interpret=True, mesh=mesh))
    for B in (8, 2, 10):  # dp=4 divides 8; 2 and 10 stay replicated
        q, k, v = _rand_qkv(rng, B, 12, 9, 4, 16)
        bias = mask_to_bias(jnp.ones((B, 9), jnp.int32).at[:, 7:].set(0))
        ref, _ = multi_head_attention(q, k, v, bias)
        rows = NamedSharding(mesh, P("dp") if B % 4 == 0 else P())
        out = sharded_kernel(
            *(jax.device_put(x, rows) for x in (q, k, v, bias)))
        assert out.sharding.spec == P("dp" if B % 4 == 0 else None,
                                      None, "tp")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_off_tpu_without_interpret_is_an_error():
    """The default compiles under Mosaic: off-TPU that raises instead of
    quietly running the Pallas interpreter (the serving path must never
    turn a missing chip into a slow success)."""
    import pytest

    rng = np.random.default_rng(0)
    q, k, v = _rand_qkv(rng, 1, 8, 8, 1, 128)
    bias = mask_to_bias(jnp.ones((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        flash_cross_attention(q, k, v, bias)


def test_pretraining_heads_skippable(tiny_config, rng):
    """compute_pretraining_heads=False drops only the masked-modeling heads."""
    model = ViLBertForVLTasks(tiny_config, dtype=jnp.float32)
    B, Nt, Nv = 2, 8, 5
    args = (
        jnp.zeros((B, Nt), jnp.int32),
        jnp.zeros((B, Nv, tiny_config.v_feature_size), jnp.float32),
        jnp.zeros((B, Nv, 5), jnp.float32),
        jnp.zeros((B, Nt), jnp.int32),
        jnp.ones((B, Nt), jnp.int32),
        jnp.ones((B, Nv), jnp.int32),
        None,
        jnp.ones((B, 1), jnp.int32),
    )
    params = model.init(rng, *args, deterministic=True)["params"]
    full = model.apply({"params": params}, *args, deterministic=True)
    lean = model.apply({"params": params}, *args, deterministic=True,
                       compute_pretraining_heads=False)
    assert lean.linguisic_prediction is None
    assert lean.vision_prediction is None
    assert full.linguisic_prediction is not None
    np.testing.assert_array_equal(np.asarray(lean.vil_prediction),
                                  np.asarray(full.vil_prediction))
    np.testing.assert_array_equal(np.asarray(lean.vision_logit),
                                  np.asarray(full.vision_logit))


def test_attention_maps_still_available_with_pallas_config(tiny_config, rng):
    """The visualization contract (reference worker.py:288) falls back to the
    probs-returning XLA path even when the Pallas flag is on."""
    cfg_p = dataclasses.replace(tiny_config, use_pallas_coattention=True,
                                pallas_interpret=True)
    B, Nt, Nv = 1, 6, 5
    args = (
        jnp.zeros((B, Nt), jnp.int32),
        jnp.zeros((B, Nv, cfg_p.v_feature_size), jnp.float32),
        jnp.zeros((B, Nv, 5), jnp.float32),
        jnp.zeros((B, Nt), jnp.int32),
        jnp.ones((B, Nt), jnp.int32),
        jnp.ones((B, Nv), jnp.int32),
        None,
        jnp.ones((B, 1), jnp.int32),
    )
    model = ViLBertForVLTasks(cfg_p, dtype=jnp.float32)
    params = model.init(rng, *args, deterministic=True)["params"]
    out = model.apply({"params": params}, *args, deterministic=True,
                      output_all_attention_masks=True)
    assert len(out.attn_data_list) == cfg_p.num_connection_layers
    assert all(p[0] is not None for p in out.attn_data_list)
