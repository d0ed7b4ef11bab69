"""The ``laguna`` family: what a cell of the sparse-expert decoder
(``model_type: laguna``: 256 routed experts with one shared, window and full
attention layers mixed, grouped heads) needs of its architecture, as
``benchmark/families/__init__.py`` lists it.

It is served by the same ``GenerateEngine`` as the ``olmo_hybrid`` family,
and what that family's docstring says of the unit of work (a token through
the layers; ``rows`` = ``max_new_tokens``, so ``rows_per_s`` is generated
tokens a second), of the one frame a request yields, of the traffic file's
keys (``deck`` of ``prompt_tokens`` and ``count``, ``jitter``,
``max_new_tokens``, ``logit_ids``) and of the teacher-forced check holds
here: the schedule, the traffic's check against the pool, the sample, the
comparison and the server's boot are that family's functions, imported
(``schedule``, ``check_traffic``, ``check``, ``server.boot``), not copied.

What differs. *The configuration file* holds the source ``config.json``'s
keys at its top level under their own names, the per-layer lists whole (48
entries: the first ``num_hidden_layers`` of them are run), and the chip's
share of the deployment: ``num_experts`` counts the routed experts *held
here* (the router keeps the ``published`` count), ``experts_held`` =
[first, count] names them, and ``vocab_size`` counts the rows of the
embedding and the head held here, from which the traffic draws its ids.
:func:`model_of` turns that into what the program's ``LagunaConfig`` and the
reference take: ``num_experts`` the router's width, ``experts_held``, the
lists cut to the depth. *The weights* are drawn leaf by leaf in bfloat16
(5.6 G parameters do not fit beside a float32 copy), the router with
``weights.router_gain`` so that its scores have unit-order spread and the
top ten differ token to token. *The work's cost*: ``flops_per_unit`` is 2 x
the matrix parameters a token really uses here: attention of every layer,
the dense MLP, the shared experts, the router, and of the routed experts
``num_experts_per_tok`` x held / published a sparse layer (5 of 10: the mean
share under even routing). The attention products and the head are left out:
a lower bound, so a share of the peak made from it cannot pass 100%.

  assets                  nothing on disk (and the program asked for the model)
  check_traffic, schedule the ``olmo_hybrid`` family's (the deck against the
                          pool; prompts, ids and logit ids from the seed)
  weights                 leaf by leaf in bfloat16, here
  boot                    ``olmo_hybrid/server.py``'s ``boot`` around
                          :func:`framework_config`
  units_since, flops_per_unit, unwritten_bytes   here
  sample, run_reference, compare, frame_of       ``olmo_hybrid/check.py``
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import math
import os
import time

from ..olmo_hybrid import (
    check,
    check_traffic,
    schedule,
    units_since,
    unwritten_bytes,
)

__all__ = ["assets", "boot", "check_traffic", "compare", "flops_per_unit",
           "frame_of", "model_of", "run_reference", "sample", "schedule",
           "units_since", "unwritten_bytes", "weights"]

sample = check.sample
compare = check.compare
frame_of = check.frame_of

# The configuration file's own keys; every other top-level key is the source
# ``config.json``'s.
OWN_KEYS = frozenset({"name", "family", "source", "reference", "published",
                      "reduced", "deployment", "assumed", "weights",
                      "engine"})
PER_LAYER = ("layer_types", "mlp_layer_types", "gating_types",
             "num_attention_heads_per_layer")


def model_of(config: dict) -> dict:
    """The model as the program and the reference take it: the source's
    keys, the per-layer lists cut to the depth that is run, ``num_experts``
    the router's (published) width and ``experts_held`` the share."""
    model = {k: v for k, v in config.items() if k not in OWN_KEYS}
    depth = model["num_hidden_layers"]
    for key in PER_LAYER:
        model[key] = list(model[key][:depth])
    model["mlp_only_layers"] = [i for i in model["mlp_only_layers"]
                                if i < depth]
    first, count = model["experts_held"]
    if count != config["num_experts"]:
        raise ValueError("experts_held counts another number of experts "
                         "than num_experts, the experts held here")
    model["num_experts"] = config["published"]["num_experts"]
    return model


def _reference(config: dict):
    return importlib.import_module(
        f"benchmark.reference.{config['reference']}")


def assets(config: dict, traffic_file: dict, cache_dir: str) -> tuple:
    """Nothing on disk: prompts are token ids drawn with the schedule, over
    the rows of the vocabulary held here. A program that cannot run the
    configuration fails here, before anything is drawn or booted."""
    from vilbert_multitask_tpu.config import LagunaConfig  # noqa: F401

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
        if name == "embed":
            return normal(k, shape, 0.0, drawn["embed_std"])
        gain = drawn["router_gain"] if name == "router" else 1.0
        return normal(k, shape, 0.0, gain / math.sqrt(shape[-2]))

    out = [draw(i, path[-1].key, shape)
           for i, (path, shape) in enumerate(leaves)]
    count = sum(math.prod(shape) for _, shape in leaves)
    return jax.tree_util.tree_unflatten(treedef, out), count


def framework_config(model: dict, engine: dict, state_dir: str,
                     rehearsal: bool):
    from vilbert_multitask_tpu.config import (
        FrameworkConfig,
        GenerateConfig,
        LagunaConfig,
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
        generate=GenerateConfig(model=LagunaConfig(**model), **engine),
        serving=serving)


def boot(config: dict, traffic_file: dict, params, assets: dict,
         state_dir: str, rehearsal: bool) -> tuple:
    from ..olmo_hybrid import server

    return server.boot(framework_config(
        model_of(config), config["engine"], state_dir, rehearsal), params)


def flops_per_unit(config: dict) -> int:
    """Matmul FLOPs of one token through the layers as this chip runs
    them: 2 x the matrix parameters it uses (module text)."""
    model = model_of(config)
    shapes = _reference(config).param_shapes(model)
    share = (model["num_experts_per_tok"] * model["experts_held"][1]
             / model["num_experts"])
    used = 0.0
    for layer in shapes["layers"]:
        for name, shape in layer.items():
            if name.endswith("norm"):
                continue
            if name.startswith("experts_"):
                used += share * math.prod(shape[1:])
            else:
                used += math.prod(shape)
    return int(2 * used)


def run_reference(config: dict, params, picked: list, assets: dict,
                  lower=None) -> list:
    return check.run_reference(model_of(config), _reference(config), params,
                               picked, lower=lower)
