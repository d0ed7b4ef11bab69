"""Analytic FLOP count of one serving forward — the MFU numerator.

Counts matmul FLOPs (2·m·n·k per dense / attention einsum) of the serving
graph: embeddings, both single-stream encoders, the co-attention bridges,
poolers, and the classifier heads — with ``compute_pretraining_heads=False``
(the serving path, engine/runtime.py) so the masked-LM/region decoders are
excluded. Elementwise/LayerNorm/softmax FLOPs are ignored; on this model
they are <2% of the matmul count, so the figure is a tight lower bound —
the conservative direction for MFU claims.

``tests/test_bench_flops.py`` pins this estimate against XLA's own
``cost_analysis()['flops']`` on the compiled serving forward.
"""

from __future__ import annotations

from vilbert_multitask_tpu.config import EngineConfig, ViLBertConfig


def _dense(n: int, d_in: int, d_out: int) -> int:
    return 2 * n * d_in * d_out


def _self_attn_layer(n: int, hidden: int, inter: int) -> int:
    """Fused-QKV self-attention + output projection + FFN (ops/attention.py,
    models/layers.py:TransformerLayer)."""
    return (
        _dense(n, hidden, 3 * hidden)  # fused qkv
        + 2 * 2 * n * n * hidden  # scores + probs·V
        + _dense(n, hidden, hidden)  # attention output projection
        + _dense(n, hidden, inter) + _dense(n, inter, hidden)  # FFN
    )


def _bridge(nt: int, nv: int, cfg: ViLBertConfig) -> int:
    """One ConnectionLayer: bi-directional cross-attention + per-stream
    output projections and FFNs (models/layers.py:ConnectionLayer)."""
    h, hv, bi = cfg.hidden_size, cfg.v_hidden_size, cfg.bi_hidden_size
    t_dir = (
        _dense(nt, h, bi)  # text queries
        + 2 * _dense(nv, hv, bi)  # image keys + values
        + 2 * 2 * nt * nv * bi  # scores + probs·V
        + _dense(nt, bi, h)  # t_output projection
    )
    v_dir = (
        _dense(nv, hv, bi)
        + 2 * _dense(nt, h, bi)
        + 2 * 2 * nv * nt * bi
        + _dense(nv, bi, hv)
    )
    ffns = (
        _dense(nt, h, cfg.intermediate_size)
        + _dense(nt, cfg.intermediate_size, h)
        + _dense(nv, hv, cfg.v_intermediate_size)
        + _dense(nv, cfg.v_intermediate_size, hv)
    )
    return t_dir + v_dir + ffns


def serving_forward_flops(
    mcfg: ViLBertConfig, ecfg: EngineConfig, batch: int
) -> int:
    """Matmul FLOPs of one compiled serving forward at batch size ``batch``
    (text always padded to ``max_text_len``, regions to ``max_regions``)."""
    nt, nv = ecfg.max_text_len, ecfg.max_regions
    per_row = 0
    # Image embeddings: feature + location projections (models/embeddings.py).
    per_row += _dense(nv, mcfg.v_feature_size, mcfg.v_hidden_size)
    per_row += _dense(nv, 5, mcfg.v_hidden_size)
    # Encoders.
    per_row += mcfg.num_hidden_layers * _self_attn_layer(
        nt, mcfg.hidden_size, mcfg.intermediate_size)
    per_row += mcfg.v_num_hidden_layers * _self_attn_layer(
        nv, mcfg.v_hidden_size, mcfg.v_intermediate_size)
    per_row += mcfg.num_connection_layers * _bridge(nt, nv, mcfg)
    # Poolers into bi_hidden (models/heads.py:Pooler).
    bi = mcfg.bi_hidden_size
    per_row += _dense(1, mcfg.hidden_size, bi) + _dense(1, mcfg.v_hidden_size, bi)
    # Classifier heads over the fused pooled vector (models/vilbert.py).
    per_row += _dense(1, bi, 2 * bi) + _dense(1, 2 * bi, mcfg.num_labels)
    per_row += _dense(1, bi, 2 * bi) + _dense(1, 2 * bi, mcfg.gqa_num_labels)
    per_row += _dense(1, bi, 1) + _dense(1, bi, 3)  # vil_logit, tri
    # Paired NLVR2 head runs on batch/2 rows of width 2·bi.
    per_row += (_dense(1, 2 * bi, 4 * bi) + _dense(1, 4 * bi, 2)) // 2
    # Per-token grounding logits (vision_logit / linguisic_logit).
    per_row += _dense(nv, mcfg.v_hidden_size, 1) + _dense(nt, mcfg.hidden_size, 1)
    return batch * per_row


# Peak dense bf16 FLOP/s per chip, keyed on jax device_kind substrings.
# Sources: published TPU spec sheets (per-chip, not per-core).
PEAK_BF16_FLOPS = (
    ("v5 lite", 197e12),  # v5e
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6 lite", 918e12),  # Trillium
    ("v6e", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
)


def peak_flops_for(device_kind: str) -> float | None:
    dk = device_kind.lower()
    for key, peak in PEAK_BF16_FLOPS:
        if key in dk:
            return peak
    return None


# Peak HBM bandwidth per chip (bytes/s), same substring keying. Sources:
# published TPU spec sheets.
PEAK_HBM_BYTES_PER_S = (
    ("v5 lite", 819e9),  # v5e
    ("v5e", 819e9),
    ("v5p", 2765e9),
    ("v6 lite", 1640e9),  # Trillium
    ("v6e", 1640e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
)

def peak_hbm_bw_for(device_kind: str) -> float | None:
    dk = device_kind.lower()
    for key, bw in PEAK_HBM_BYTES_PER_S:
        if key in dk:
            return bw
    return None


def _peaks_for(device_kind: str) -> tuple:
    """(peak FLOP/s, peak HBM bytes/s) for a device in the tables above.
    A device that is not in them is an error, not a default: a caller that
    wants the analytic roofline of a NAMED chip off that chip (a CPU smoke
    reasoning about v5e) passes that chip's name."""
    peak = peak_flops_for(device_kind)
    bw = peak_hbm_bw_for(device_kind)
    if peak is None or bw is None:
        raise ValueError(
            f"no peak FLOP/s / HBM bandwidth entry for device kind "
            f"{device_kind!r}; pass the name of a chip in "
            f"engine/flops.py's tables (e.g. 'TPU v5e')")
    return peak, bw


def param_tree_bytes(params) -> int:
    """Total bytes of a device param tree — the weight-read term of the
    serving roofline (every forward reads every parameter once).

    Dtype-aware by construction: it sums what the tree actually stores, so
    an int8 tree (quant.py ``{"int8", "scale"}`` pairs — 1-byte values plus
    their f32 scale vectors) reports its real HBM footprint, bf16 reports
    half of f32, with no per-mode special casing."""
    import jax

    return int(sum(
        leaf.size * jax.numpy.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(params)))


def weight_bytes_per_row(param_bytes: int, batch: int) -> float:
    """HBM weight bytes amortized per batch row at ``batch`` — the number
    bigger batches and smaller storage dtypes both shrink."""
    return param_bytes / max(1, batch)


def knee_rows(mcfg: ViLBertConfig, ecfg: EngineConfig, device_kind: str,
              param_bytes: int) -> int:
    """The batch size where the roofline verdict flips from
    weight-read-bound to compute-bound: the smallest ``batch`` with
    ``t_compute >= t_mem``. FLOPs are linear in batch
    (:func:`serving_forward_flops`) while the weight-read term is flat, so
    the knee is analytic: ``ceil(param_bytes · peak / (bw · flops_per_row))``.
    Raises ``ValueError`` for a device kind the peak tables do not know."""
    import math

    peak, bw = _peaks_for(device_kind)
    flops_per_row = serving_forward_flops(mcfg, ecfg, 1)
    return max(1, math.ceil(param_bytes * peak / (bw * flops_per_row)))
