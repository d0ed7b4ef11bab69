"""Sparse experts: the router, and the expert layer *that is told which
experts it holds*.

A token's router scores run over all ``E`` choices of the model; its top
``k`` and their weights are taken over all of them (:func:`route`): by
default the softmax's ``k`` largest, renormalised to sum to 1 and scaled.
Two further forms of the same function: a *selection bias*, added to the
logits to pick the ``k`` and never to weight them (the weights stay the
softmax's of the logits alone), and weights left unrenormalised (a top-1
weight renormalised is 1, which would cut the router out of the forward).
A choice the router may make that no chip holds (an id at or past the
experts held: a "no expert" choice) is dropped by the expert layer like an
expert some other chip holds: it adds nothing. The
expert layer (:func:`experts_forward`) holds the weights of the experts
``first .. first + held - 1`` only, and computes the part of the result
those give: ``sum over e in top-k(t) and held of w[t, e] * E_e(x[t])``,
``E_e(x) = W_down_e (SiLU(W_gate_e x) * W_up_e x)``. What the other experts
would have added is some other chip's to compute and is not stood in for:
with every expert held the sum is the whole layer's. No token is dropped
and there is no capacity factor: the buffers are sized for every pair
landing here.

On the chip the (token, expert) *pairs* whose expert is held are sorted by
expert into a row buffer in which every expert's rows start at a multiple
of the tile (``tile`` rows: what one grid step multiplies), so that a tile
belongs to one expert. One Pallas kernel (``moe_experts`` in a profile)
walks the tiles in use: a step holds its expert's ``[H, 2W]`` gate-and-up
matrix and ``[W, H]`` down matrix in VMEM (fetched once an expert: tiles of
one expert follow each other and the pipeline keeps the block), multiplies
the tile's rows through both, and writes ``[tile, H]`` float32. Steps past
the last tile in use compute and move nothing (their blocks name the last
tile's). Operands are the weights' dtype (bfloat16), accumulation float32.
The rows are gathered back by pair and summed under the router's weights.

:func:`experts_oracle` is the same sum in plain ``jax.numpy``: every held
expert over every token under a mask. It is the CPU's path and the tests'
oracle.

Both return, beside the result, three small integers a call (``stats``
[3], int32): pairs computed here, experts touched, and the fullest expert's
pairs: what ``vmt_moe_*`` count (``engine/generate.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One expert's matrices twice over (the pipeline's double buffer; 37.7 MB
# at the served size) beside the tile's rows and products.
_VMEM_LIMIT = 96 * 1024 * 1024


def route(logits, k: int, scale: float = 1.0, *, bias=None,
          renormalize: bool = True):
    """``logits`` [T, E] float32 -> (experts [T, k] int32, weights [T, k]
    float32): softmax over all ``E``, the ``k`` largest, their scores
    renormalised to sum to 1 and scaled by ``scale``. With ``bias`` [E] the
    ``k`` are the largest of ``logits + bias`` and their weights the
    softmax's scores of them; without ``renormalize`` the scores are only
    scaled."""
    scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if bias is None:
        top, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(logits.astype(jnp.float32) + bias, k)
        top = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalize:
        weights = scale * top / jnp.sum(top, axis=-1, keepdims=True)
    else:
        weights = scale * top
    return experts.astype(jnp.int32), weights


def _local(experts, real, held):
    """The pairs' experts numbered among the held ones; ``count`` (the
    sentinel) where the expert is not held or the token is padding."""
    first, count = held
    local = experts - first
    here = (local >= 0) & (local < count) & real[:, None]
    return jnp.where(here, local, count), here


def _stats(sizes):
    return jnp.stack([sizes.sum(), (sizes > 0).sum(),
                      sizes.max()]).astype(jnp.int32)


def experts_oracle(x, experts, weights, held, w_gate_up, w_down, real=None):
    """The held experts' share of the layer, plainly: every held expert
    over every token, summed under a mask. ``x`` [T, H]; ``experts``,
    ``weights`` [T, k]; ``held`` = (first, count); ``w_gate_up`` [count, H,
    2W] (gate | up); ``w_down`` [count, W, H]; ``real`` [T] bool (None:
    every token). Returns (result [T, H] float32, stats [3])."""
    T = x.shape[0]
    real = jnp.ones((T,), bool) if real is None else real
    local, here = _local(experts, real, held)
    count, W = held[1], w_down.shape[1]
    # [T, count]: the token's weight on each held expert (0 where not routed)
    share = jnp.zeros((T, count + 1), jnp.float32).at[
        jnp.arange(T)[:, None], local].add(jnp.where(here, weights, 0.0)
                                           )[:, :count]
    gu = jnp.einsum("th,ehw->etw", x.astype(w_gate_up.dtype), w_gate_up,
                    preferred_element_type=jnp.float32)
    h = jax.nn.silu(gu[..., :W]) * gu[..., W:]
    y = jnp.einsum("etw,ewh->eth", h.astype(w_down.dtype), w_down,
                   preferred_element_type=jnp.float32)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[local.ravel()].add(1)
    return jnp.einsum("eth,te->th", y, share), _stats(sizes[:count])


def tile_rows(pairs: int, held: int) -> int:
    """Rows a grid step multiplies: about what an expert sees under even
    routing, a power of two from 16 (a bfloat16 operand tile's sublanes) to
    128 (the MXU's rows)."""
    mean = max(1, pairs // max(held, 1))
    return min(128, max(16, 1 << (mean - 1).bit_length()))


def _kernel(tile_expert_ref, tiles_ref, x_ref, gu_ref, down_ref, o_ref):
    del tile_expert_ref                 # the index maps' alone

    @pl.when(pl.program_id(0) < tiles_ref[0])
    def _():
        W = down_ref.shape[0]
        gu = jnp.dot(x_ref[...], gu_ref[...],
                     preferred_element_type=jnp.float32)
        h = jax.nn.silu(gu[:, :W]) * gu[:, W:]
        o_ref[...] = jnp.dot(h.astype(down_ref.dtype), down_ref[...],
                             preferred_element_type=jnp.float32)


def experts_forward(x, experts, weights, held, w_gate_up, w_down, real=None,
                    *, interpret: bool = False):
    """As :func:`experts_oracle`, by the sorted pairs and one grouped
    product over the held experts (module text)."""
    T, H = x.shape
    k = experts.shape[1]
    count, W = held[1], w_down.shape[1]
    N = T * k
    tm = tile_rows(N, count)
    # Every expert's rows padded to whole tiles: at most count tiles more.
    n_tiles = -(-N // tm) + count
    real = jnp.ones((T,), bool) if real is None else real
    local, here = _local(experts, real, held)
    local = local.ravel()                               # [N], pair t * k + j
    sizes = jnp.zeros((count + 1,), jnp.int32).at[local].add(1)[:count]
    padded = -(-sizes // tm) * tm
    ends = jnp.cumsum(padded)                           # rows, tile-aligned
    starts = ends - padded
    firsts = jnp.cumsum(sizes) - sizes                  # among sorted pairs
    order = jnp.argsort(local, stable=True)             # held pairs first
    by_expert = local[order]
    rank = jnp.arange(N, dtype=jnp.int32)
    e = jnp.minimum(by_expert, count - 1)
    row_of_sorted = jnp.where(by_expert < count,
                              starts[e] + rank - firsts[e], n_tiles * tm)
    # Which token each row of the buffer is (0 in the padding: finite rows
    # nobody reads), and where each pair's row went (out of range: not here).
    row_token = jnp.zeros((n_tiles * tm,), jnp.int32).at[row_of_sorted].set(
        (order // k).astype(jnp.int32), mode="drop")
    row_of_pair = jnp.zeros((N,), jnp.int32).at[order].set(row_of_sorted)
    tiles = (ends[-1] // tm).astype(jnp.int32)
    tile_start = jnp.arange(n_tiles, dtype=jnp.int32) * tm
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.minimum(tile_start, ends[-1] - 1),
                         side="right"), count - 1).astype(jnp.int32)
    rows = x.astype(w_gate_up.dtype)[row_token]         # [n_tiles * tm, H]

    def tile_block(i, tile_expert, tiles):
        # Past the last tile in use: the last one's blocks, so nothing moves.
        return (jnp.maximum(jnp.minimum(i, tiles[0] - 1), 0), 0)

    def expert_block(i, tile_expert, tiles):
        return (tile_expert[i], 0, 0)

    y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((tm, H), tile_block),
                      pl.BlockSpec((None, H, 2 * W), expert_block),
                      pl.BlockSpec((None, W, H), expert_block)],
            out_specs=pl.BlockSpec((tm, H), tile_block)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tm, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="moe_experts",
        interpret=interpret,
    )(tile_expert, tiles.reshape(1), rows, w_gate_up, w_down)
    # Back by pair: rows of pairs not computed here are never read.
    picked = jnp.take(y, row_of_pair.reshape(T, k), axis=0, mode="fill",
                      fill_value=0.0)                   # [T, k, H]
    out = jnp.einsum("tkh,tk->th", picked,
                     jnp.where(here, weights, 0.0))
    return out, _stats(sizes)
