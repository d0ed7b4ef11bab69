"""The ``zaya`` family: what a cell of the CCA decoder (``model_type:
zaya``: compressed convolutional attention over two key/value heads, then
one of sixteen experts or none, chosen by an MLP router whose state is
averaged from layer to layer) needs of its architecture, as
``benchmark/families/__init__.py`` lists it.

It is served by the same ``GenerateEngine`` as the ``olmo_hybrid`` family,
and what that family's docstring says of the unit of work (a token through
the layers; ``rows`` = ``max_new_tokens``, so ``rows_per_s`` is generated
tokens a second), of the one frame a request yields, of the traffic file's
keys (``deck`` of ``prompt_tokens`` and ``count``, ``jitter``,
``max_new_tokens``, ``logit_ids``) and of the teacher-forced check holds
here: the schedule, the traffic's check against the pool, the sample, the
comparison and the server's boot are that family's functions, imported
(``schedule``, ``check_traffic``, ``check``, ``server``), not copied.

What differs. *The configuration file* holds the source ``config.json``'s
keys at its top level under their own names (``layer_types`` whole, 40
entries: the first ``num_hidden_layers`` of them are run) and the three
flags of the sibling configuration that name the router's parts and the
residual merge (``assumed`` says why). *The weights* are drawn leaf by leaf
in bfloat16, in the ranges the configuration's ``weights`` gives.
*The work's cost*: ``flops_per_unit`` is 2 x the matrix parameters a token
uses: CCA's projections and its second convolution, the router's four maps
and one expert (the routed expert counted at one a token, although some
tokens choose none). The attention products and the head are left out: a
lower bound, so a share of the peak made from it cannot pass 100%. *The
check* prints beside the judged numbers ``top1_flip_share``: of the
shortest sampled request's token-layers, the share whose router choice
differs between the reference in float32 and the reference with its
products' operands rounded to bfloat16 (the program's precision): how
often a near tie turns a whole expert, which is what the spikes of
``logit_err_max`` are made of. It is not judged.

  assets                  nothing on disk (and the program asked for the model)
  check_traffic           the ``olmo_hybrid`` family's
  schedule                here: that family's requests, the passes' order
                          and the lengths the same for every seed
  weights                 leaf by leaf in bfloat16, here
  boot                    ``olmo_hybrid/server.py``'s ``boot`` around
                          :func:`framework_config`
  units_since, unwritten_bytes                   the ``olmo_hybrid`` family's
  flops_per_unit, run_reference, compare         here
  sample, frame_of                               ``olmo_hybrid/check.py``
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import math
import os
import time

import collections
import itertools

from ...harness import arrivals
from ..olmo_hybrid import check, check_traffic, units_since, unwritten_bytes
from ..olmo_hybrid.traffic import GENERATE_TASK_ID

__all__ = ["assets", "boot", "check_traffic", "compare", "flops_per_unit",
           "frame_of", "model_of", "run_reference", "sample", "schedule",
           "units_since", "unwritten_bytes", "weights"]

sample = check.sample
frame_of = check.frame_of

# The configuration file's own keys; every other top-level key is the source
# ``config.json``'s (or one of the sibling's flags).
OWN_KEYS = frozenset({"name", "family", "source", "reference", "published",
                      "reduced", "deployment", "assumed", "weights",
                      "engine"})
# What ``top1_flip_share`` is read over: the shortest sampled request.
FLIP_PRECISION = "bf16"
# The stream that orders every pass of the deck and draws the prompts'
# lengths: one for every seed.
PASS_ORDER = 0


def model_of(config: dict) -> dict:
    """The model as the program and the reference take it: the source's
    keys, ``layer_types`` cut to the depth that is run."""
    model = {k: v for k, v in config.items() if k not in OWN_KEYS}
    model["layer_types"] = list(
        model["layer_types"][:model["num_hidden_layers"]])
    return model


def _reference(config: dict):
    return importlib.import_module(
        f"benchmark.reference.{config['reference']}")


def assets(config: dict, traffic_file: dict, cache_dir: str) -> tuple:
    """Nothing on disk: prompts are token ids drawn with the schedule. A
    program that cannot run the configuration fails here, before anything
    is drawn or booted."""
    from vilbert_multitask_tpu.config import ZayaConfig  # noqa: F401

    t = time.monotonic()
    return ({"vocab_size": config["vocab_size"]},
            {"assets_s": time.monotonic() - t})


def schedule(traffic_file: dict, seed: int, seconds: float,
             assets: dict) -> dict:
    """The ``olmo_hybrid`` family's requests (its ``traffic.py``: the deck
    dealt pass after pass, each prompt its entry's length less up to
    ``jitter``, no two of a pass equal, ids and logit ids uniform), with
    one difference: **the order of every pass and the prompts' lengths are
    drawn from one fixed stream, the same for every seed; the seed draws
    the ids.** With 16 callers and prompts from 8k to 127k tokens the
    prefill is a queue of very unequal jobs whose cost grows with the
    square of their length, and a seed-drawn order and seed-drawn lengths
    moved the requests answered in a 51 s window over 51-56 on one TPU
    v5e (IQR/median 7.8%; the order alone fixed, 4.8%), against the 3% a
    new cell may spread."""
    rng = arrivals.rng_for(seed, 1)
    fixed = arrivals.rng_for(PASS_ORDER, 1)
    deck, vocab = traffic_file["deck"], assets["vocab_size"]
    made = itertools.count()
    recent: collections.deque = collections.deque(
        maxlen=sum(int(k["count"]) for k in deck))
    new, n_ids = int(traffic_file["max_new_tokens"]), int(
        traffic_file["logit_ids"])

    def request(kind: dict) -> dict:
        nominal = int(kind["prompt_tokens"])
        room = max(1, int(nominal * float(traffic_file["jitter"])))
        for _ in range(16):
            length = nominal - int(fixed.integers(0, room))
            if length not in recent:
                break
        recent.append(length)
        name = f"doc-{next(made)}"
        body = {"task_id": GENERATE_TASK_ID, "question": name,
                "prompt_ids": rng.integers(0, vocab, length).tolist(),
                "max_new_tokens": new,
                "logit_ids": rng.choice(vocab, size=n_ids,
                                        replace=False).tolist()}
        return {"body": body, "key": name, "key_field": "question",
                "rows": new, "kind": (nominal,),
                "deck_count": int(kind["count"])}

    def sessions(n: int) -> list:
        kinds = arrivals.deal(deck, n)
        at = [start + int(pos) for start in range(0, n, recent.maxlen)
              for pos in fixed.permutation(min(recent.maxlen, n - start))]
        return [[request(kinds[pos])
                 for _ in range(int(traffic_file["questions_per_session"]))]
                for pos in at]

    return arrivals.schedule(traffic_file, rng, seconds, sessions)


def weights(config: dict, seed: int) -> tuple:
    """(the served tree on the device, its parameter count). Drawn leaf by
    leaf in the stored type, the key folded by leaf; the ranges are the
    configuration's ``weights`` (``assumed`` says why)."""
    import jax
    import jax.numpy as jnp

    from ...harness.weights import seed_key

    dtype = jnp.dtype(config["engine"]["param_dtype"])
    drawn = config["weights"]
    model = model_of(config)
    shapes = _reference(config).param_shapes(model)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    key = seed_key(seed)

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def normal(k, shape, mean, std):
        return (mean + std * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    def draw(i, name, shape):
        k = jax.random.fold_in(key, i)
        if name.endswith(("norm", "_scale")):
            return normal(k, shape, 1.0, drawn["norm_scale_std"])
        if name == "tau":
            root = math.sqrt(model["head_dim"])
            return normal(k, shape, root, root * drawn["tau_std"])
        if name == "router_eda":
            return normal(k, shape, *drawn["eda_gamma"])
        if name == "router_bias":
            return normal(k, shape, 0.0, drawn["router_bias_std"])
        if name == "conv0":
            return normal(k, shape, 0.0, drawn["conv0_std"])
        if name == "embed":
            return normal(k, shape, 0.0,
                          drawn["embed_gain"] / math.sqrt(shape[-1]))
        gain = drawn["router_gain"] if name == "router_out" else 1.0
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
        ServingConfig,
        ZayaConfig,
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
        generate=GenerateConfig(model=ZayaConfig(**model), **engine),
        serving=serving)


def boot(config: dict, traffic_file: dict, params, assets: dict,
         state_dir: str, rehearsal: bool) -> tuple:
    from ..olmo_hybrid import server

    return server.boot(framework_config(
        model_of(config), config["engine"], state_dir, rehearsal), params)


# The matrices a token multiplies by once a layer; the experts' are counted
# apart, at one expert a token.
_MATRICES = ("wqk", "wv", "wo", "conv1", "router", "router_mlp1",
             "router_mlp2", "router_out")


def flops_per_unit(config: dict) -> int:
    """Matmul FLOPs of one token through the layers: 2 x the matrix
    parameters it uses (module text)."""
    model = model_of(config)
    shapes = _reference(config).param_shapes(model)
    used = 0
    for layer in shapes["layers"]:
        used += sum(math.prod(layer[name]) for name in _MATRICES)
        used += sum(math.prod(layer[name][1:])
                    for name in ("experts_gate_up", "experts_down"))
    return int(2 * used)


def run_reference(config: dict, params, picked: list, assets: dict,
                  lower=None) -> list:
    """``olmo_hybrid/check.py``'s teacher-forced reference; beside it, where
    no lower precision is asked for, the router's choices over the shortest
    picked request in float32 and in the program's precision."""
    model, reference = model_of(config), _reference(config)
    outputs = check.run_reference(model, reference, params, picked,
                                  lower=lower)
    answered = [(len(r["body"]["prompt_ids"]), i)
                for i, (r, out) in enumerate(zip(picked, outputs))
                if out is not None]
    if lower is None and answered:
        import jax

        r = picked[min(answered)[1]]
        seq = r["body"]["prompt_ids"] + r["served_tokens"][:-1]
        with jax.default_matmul_precision("highest"):
            _, exact = reference.forward(params, model, seq, rows=[0],
                                         choices=True)
            _, rounded = reference.forward(params, model, seq, rows=[0],
                                           lower=FLIP_PRECISION,
                                           choices=True)
        outputs[min(answered)[1]]["flips"] = (
            int((exact != rounded).sum()), int(exact.size))
    return outputs


def compare(picked: list, stamps: dict, outputs: list) -> dict:
    numbers = check.compare(picked, stamps, outputs)
    flips = [out["flips"] for out in outputs
             if out is not None and "flips" in out]
    if flips:
        numbers["top1_flip_share"] = flips[0][0] / max(flips[0][1], 1)
        numbers["top1_flip_token_layers"] = flips[0][1]
    return numbers
