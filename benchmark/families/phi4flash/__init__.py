"""The ``phi4flash`` family: what a cell of the decoder-hybrid-decoder
(``model_type: phi4flash``: a self-decoder of Mamba mixers and differential
attention, a cross-decoder of gated memory units and differential
cross-attention that reads one layer's scan output and one layer's keys and
values) needs of its architecture, as ``benchmark/families/__init__.py``
lists it.

It is served by the same ``GenerateEngine`` as the ``olmo_hybrid`` and
``laguna`` families, and what the ``olmo_hybrid`` family's docstring says of
the unit of work (a token through the layers; ``rows`` = ``max_new_tokens``,
so ``rows_per_s`` is generated tokens a second), of the one frame a request
yields, of the traffic file's keys (``deck`` of ``prompt_tokens`` and
``count``, ``jitter``, ``max_new_tokens``, ``logit_ids``) and of the
teacher-forced check holds here: the schedule, the traffic's check against
the pool, the comparison and the server's boot are that family's functions,
imported (``schedule``, ``check_traffic``, ``check``, ``server.boot``), not
copied.

What differs. *The configuration file* holds the source ``config.json``'s
keys at its top level under their own names and, beside them, the Mamba
mixer's sizes, which the source does not give (``mamba_d_state``,
``mamba_d_conv``, ``mamba_expand``, ``mamba_dt_rank``; ``assumed`` says
where each comes from). *The weights* are drawn leaf by leaf in bfloat16;
the embedding, which is the head too, with spread ``embed_gain / sqrt(hidden
size)`` so that logits have unit-order spread; ``A_log`` is the logarithm of
1 .. ``d_state`` a channel and ``dt_bias`` such that ``dt`` lies in the
``dt`` range, as Mamba initialises both. *The work's cost*: a decoded token
runs every layer and the head, ``flops_per_unit`` = 2 x all their matrix
parameters (attention products, the scan and the norms left out: a lower
bound); a prompt token runs the self-decoder only, so ``units_since``
counts it as the self-decoder's share of that (0.51 at the published
sizes; the one cross-decoder row a prompt is left out), and a share of the
peak made from the two cannot pass 100%. *The sample* takes two requests of
every kind of the deck before it fills up with the longest: a deck whose
short kinds are most of its entries would otherwise never have them
compared.

  assets                  nothing on disk (and the program asked for the model)
  check_traffic, schedule the ``olmo_hybrid`` family's
  weights                 leaf by leaf in bfloat16, here
  boot                    ``olmo_hybrid/server.py``'s ``boot`` around
                          :func:`framework_config`
  units_since, flops_per_unit   here; ``unwritten_bytes`` ``olmo_hybrid``'s
  sample                  here; ``run_reference``, ``compare``, ``frame_of``
                          ``olmo_hybrid/check.py``
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import math
import os
import time

from ...harness import arrivals
from ..olmo_hybrid import (
    _tokens_in,
    check,
    check_traffic,
    schedule,
    unwritten_bytes,
)

__all__ = ["assets", "boot", "check_traffic", "compare", "flops_per_unit",
           "frame_of", "model_of", "run_reference", "sample", "schedule",
           "units_since", "unwritten_bytes", "weights"]

compare = check.compare
frame_of = check.frame_of

# The configuration file's own keys; every other top-level key is the
# model's (the source ``config.json``'s, and the assumed Mamba sizes).
OWN_KEYS = frozenset({"name", "family", "source", "reference", "reduced",
                      "deployment", "assumed", "weights", "engine"})
# Leaves with two axes that no matrix product reads.
NOT_MATRICES = frozenset({"conv_w", "A_log"})
# Requests of every kind the sample takes before the longest fill it up.
EACH_KIND = 2


def model_of(config: dict) -> dict:
    return {k: v for k, v in config.items() if k not in OWN_KEYS}


def _reference(config: dict):
    return importlib.import_module(
        f"benchmark.reference.{config['reference']}")


def assets(config: dict, traffic_file: dict, cache_dir: str) -> tuple:
    """Nothing on disk: prompts are token ids drawn with the schedule. A
    program that cannot run the configuration fails here, before anything
    is drawn or booted."""
    from vilbert_multitask_tpu.config import Phi4FlashConfig  # noqa: F401

    t = time.monotonic()
    return ({"vocab_size": config["vocab_size"]},
            {"assets_s": time.monotonic() - t})


def weights(config: dict, seed: int) -> tuple:
    """(the served tree on the device, its parameter count). Drawn leaf by
    leaf in the stored type, the key folded by leaf; the ranges are the
    configuration's ``weights`` (``assumed`` says why)."""
    import jax
    import jax.numpy as jnp

    from ...harness.weights import seed_key

    dtype = jnp.dtype(config["engine"]["param_dtype"])
    drawn = config["weights"]
    shapes = _reference(config).param_shapes(model_of(config))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    key = seed_key(seed)

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(k, shape, mean, std):
        return (mean + std * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    def draw(i, name, shape):
        k = jax.random.fold_in(key, i)
        if name.endswith("norm"):
            return normal(k, shape, 1.0, drawn["norm_scale_std"])
        if name == "D":
            return normal(k, shape, 1.0, drawn["d_std"])
        if name == "A_log":
            rates = jnp.arange(1.0, shape[0] + 1.0)
            return jnp.broadcast_to(jnp.log(rates)[:, None], shape
                                    ).astype(dtype)
        if name == "dt_bias":     # softplus^-1 of the drawn step
            lo, hi = drawn["dt"]
            dt = jnp.exp(jax.random.uniform(
                k, shape, minval=math.log(lo), maxval=math.log(hi)))
            return jnp.log(jnp.expm1(dt)).astype(dtype)
        if name.startswith("lambda"):
            return normal(k, shape, 0.0, drawn["lambda_std"])
        if len(shape) == 1:
            return normal(k, shape, 0.0, drawn["bias_std"])
        if name == "embed":
            return normal(k, shape, 0.0,
                          drawn["embed_gain"] / math.sqrt(shape[-1]))
        return normal(k, shape, 0.0, 1.0 / math.sqrt(shape[-2]))

    out = [draw(i, path[-1].key, shape)
           for i, (path, shape) in enumerate(leaves)]
    count = sum(math.prod(shape) for _, shape in leaves)
    return jax.tree_util.tree_unflatten(treedef, out), count


def framework_config(model: dict, engine: dict, state_dir: str,
                     rehearsal: bool):
    from vilbert_multitask_tpu.config import (
        FrameworkConfig,
        GenerateConfig,
        Phi4FlashConfig,
        ServingConfig,
    )

    model = dict(model)
    if rehearsal:
        # The CPU rehearsal says so itself: the kernels in the interpreter.
        model["pallas_interpret"] = True
    engine = dict(engine)
    for key in ("prefill_buckets", "decode_buckets"):
        engine[key] = tuple(engine[key])
    serving = dataclasses.replace(
        ServingConfig(),
        queue_db_path=os.path.join(state_dir, "queue.sqlite3"),
        results_db_path=os.path.join(state_dir, "results.sqlite3"),
        media_root=os.path.join(state_dir, "media"),
        http_port=0, ws_port=0)
    return FrameworkConfig(
        generate=GenerateConfig(model=Phi4FlashConfig(**model), **engine),
        serving=serving)


def boot(config: dict, traffic_file: dict, params, assets: dict,
         state_dir: str, rehearsal: bool) -> tuple:
    from ..olmo_hybrid import server

    return server.boot(framework_config(
        model_of(config), config["engine"], state_dir, rehearsal), params)


def _matrices(shapes: dict) -> tuple:
    """(matrix parameters of the self-decoder's layers, of all the layers
    and the head, which is the embedding) of a tree of shapes."""
    a_layer = [sum(math.prod(shape) for name, shape in layer.items()
                   if len(shape) == 2 and name not in NOT_MATRICES)
               for layer in shapes["layers"]]
    self_decoder = len(a_layer) // 2 + 2
    return (sum(a_layer[:self_decoder]),
            sum(a_layer) + math.prod(shapes["embed"]))


def flops_per_unit(config: dict) -> int:
    """Matmul FLOPs of one decoded token: 2 x the matrix parameters of
    every layer and of the tied head."""
    return 2 * _matrices(
        _reference(config).param_shapes(model_of(config)))[1]


def units_since(app, since: float) -> float:
    """Decoded tokens, and prompt tokens at the self-decoder's share of a
    decoded token's FLOPs (of the tree that is served)."""
    mine, whole = _matrices({
        "embed": app.generate_engine.params["embed"].shape,
        "layers": [{name: leaf.shape for name, leaf in layer.items()}
                   for layer in app.generate_engine.params["layers"]]})
    return (_tokens_in("vmt_decode_batch_fill", since)
            + mine / whole * _tokens_in("vmt_prefill_chunk_fill", since))


def sample(requests: list, stamps: dict, seed: int, limit: int) -> list:
    """Answered window requests drawn from the seed: ``EACH_KIND`` of every
    kind of the deck, then the longest kinds first until ``limit``; each
    with the tokens that were served for it."""
    answered = [r for r in requests if r["i"] in stamps]
    arrivals.rng_for(seed, 2).shuffle(answered)
    by_kind: dict = {}
    for r in answered:
        by_kind.setdefault(tuple(r["kind"]), []).append(r)
    kinds = sorted(by_kind, reverse=True)
    picked = [r for kind in kinds for r in by_kind[kind][:EACH_KIND]]
    rest = [r for kind in kinds for r in by_kind[kind][EACH_KIND:]]
    picked = (picked + rest)[:limit]
    return [dict(r, served_tokens=list(
        (stamps[r["i"]].get("result") or {}).get("tokens") or []))
        for r in picked]


def run_reference(config: dict, params, picked: list, assets: dict,
                  lower=None) -> list:
    return check.run_reference(model_of(config), _reference(config), params,
                               picked, lower=lower)
