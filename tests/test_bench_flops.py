"""The MFU numerator must be real: pin the analytic matmul-FLOP count
(engine/flops.py) against XLA's own cost model for the compiled serving
forward. The analytic count ignores elementwise ops, so it must come in at
or just under XLA's figure — never above it (an overcount would inflate
every MFU number derived from it)."""

import numpy as np
import pytest

from vilbert_multitask_tpu.config import EngineConfig, FrameworkConfig
from vilbert_multitask_tpu.engine.flops import (
    knee_rows,
    peak_flops_for,
    serving_forward_flops,
)
from vilbert_multitask_tpu.engine.runtime import InferenceEngine


@pytest.mark.parametrize("batch", [1, 2])
def test_flops_estimate_vs_xla_cost_analysis(tiny_config, batch):
    cfg = FrameworkConfig(
        model=tiny_config,
        engine=EngineConfig(
            compute_dtype="float32", max_regions=11,
            use_pallas_coattention=False, use_pallas_self_attention=False,
        ),
    )
    eng = InferenceEngine(cfg, seed=0)
    d = eng._dummy_batch(batch)
    fwd = eng._forward(batch, False)
    compiled = fwd.lower(eng.params, eng.head_slabs, d).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # older jax returns [dict]
        cost = cost[0]
    xla_flops = float(cost["flops"])
    est = serving_forward_flops(cfg.model, cfg.engine, batch)
    # Lower bound, but a tight one: within 2% above is measurement noise in
    # XLA's model; more than 40% below means a missing term.
    assert est <= xla_flops * 1.02, (est, xla_flops)
    assert est >= 0.6 * xla_flops, (est, xla_flops)


def test_flops_scale_linearly_in_batch(tiny_config):
    e = EngineConfig()
    one = serving_forward_flops(tiny_config, e, 1)
    ten = serving_forward_flops(tiny_config, e, 10)
    assert ten == 10 * one
    # Flagship config sanity: a serving forward is tens of GFLOPs per row.
    from vilbert_multitask_tpu.config import ViLBertConfig

    full = serving_forward_flops(ViLBertConfig(), e, 1)
    assert 10e9 < full < 500e9, full


def test_peak_lookup():
    assert peak_flops_for("TPU v5 lite") == 197e12
    assert peak_flops_for("TPU v4") == 275e12
    assert peak_flops_for("cpu") is None
    assert np.isfinite(peak_flops_for("TPU v6 lite"))


def test_knee_refuses_a_device_it_does_not_know(tiny_config):
    """An unknown device kind is an error, never a silently substituted
    reference chip; a caller that wants a named chip's analytic knee
    off that chip passes the chip's name."""
    e = EngineConfig()
    for kind in ("cpu", "TPU v99"):
        with pytest.raises(ValueError, match="no peak"):
            knee_rows(tiny_config, e, kind, 10**6)
    assert knee_rows(tiny_config, e, "TPU v5e", 10**6) >= 1
