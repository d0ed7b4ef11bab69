"""The served forward's operations, from shapes alone: the numerator of
``*_mfu``. It is the benchmark's own copy of the arithmetic in the program's
``engine/flops.py`` (a test holds the two equal today), so that a later
change to the program cannot move the yardstick. (A kernel's operations and
bytes, the numerator of ``*_roofline``, are in ``reduce/flops.py``.)

Matmul FLOPs only (2·m·n·k per dense layer or attention product) of the
serving graph: embeddings, both streams, the bridges, poolers and the served
heads; the masked-modelling decoders are not served and not counted.
Elementwise work, LayerNorm and softmax are left out (under 2% here), so
every share built on this is a slight under-statement.
"""

from __future__ import annotations


def _dense(n: int, d_in: int, d_out: int) -> int:
    return 2 * n * d_in * d_out


def _self_layer(n: int, hidden: int, inter: int) -> int:
    return (_dense(n, hidden, 3 * hidden) + 2 * 2 * n * n * hidden
            + _dense(n, hidden, hidden)
            + _dense(n, hidden, inter) + _dense(n, inter, hidden))


def _bridge(nt: int, nv: int, m: dict) -> int:
    h, hv, bi = m["hidden_size"], m["v_hidden_size"], m["bi_hidden_size"]
    t_dir = (_dense(nt, h, bi) + 2 * _dense(nv, hv, bi)
             + 2 * 2 * nt * nv * bi + _dense(nt, bi, h))
    v_dir = (_dense(nv, hv, bi) + 2 * _dense(nt, h, bi)
             + 2 * 2 * nv * nt * bi + _dense(nv, bi, hv))
    ffns = (_dense(nt, h, m["intermediate_size"])
            + _dense(nt, m["intermediate_size"], h)
            + _dense(nv, hv, m["v_intermediate_size"])
            + _dense(nv, m["v_intermediate_size"], hv))
    return t_dir + v_dir + ffns


def forward_flops_per_row(model: dict, engine: dict) -> int:
    """Matmul FLOPs of one image row through the served forward (text padded
    to ``max_text_len``, regions to ``max_regions``)."""
    m = model
    nt, nv = engine["max_text_len"], engine["max_regions"]
    bi = m["bi_hidden_size"]
    total = _dense(nv, m["v_feature_size"], m["v_hidden_size"])
    total += _dense(nv, 5, m["v_hidden_size"])
    total += m["num_hidden_layers"] * _self_layer(
        nt, m["hidden_size"], m["intermediate_size"])
    total += m["v_num_hidden_layers"] * _self_layer(
        nv, m["v_hidden_size"], m["v_intermediate_size"])
    total += len(m["t_biattention_id"]) * _bridge(nt, nv, m)
    total += _dense(1, m["hidden_size"], bi) + _dense(1, m["v_hidden_size"], bi)
    total += _dense(1, bi, 2 * bi) + _dense(1, 2 * bi, m["num_labels"])
    total += _dense(1, bi, 2 * bi) + _dense(1, 2 * bi, m["gqa_num_labels"])
    total += _dense(1, bi, 1) + _dense(1, bi, 3)
    total += (_dense(1, 2 * bi, 4 * bi) + _dense(1, 4 * bi, 2)) // 2
    total += _dense(nv, m["v_hidden_size"], 1) + _dense(nt, m["hidden_size"], 1)
    return total
