"""The ``olmo_hybrid`` family: what a cell of the hybrid decoder (gated
linear attention in three layers of four, ``model_type: olmo_hybrid``)
needs of its architecture, as ``benchmark/families/__init__.py`` lists it.

What this family adds to ``benchmark/README.md`` (which a ``model_config`` PR
may not edit). *The unit of work is a token through the layers*: a request's
``rows`` is its ``max_new_tokens``, so ``rows_per_s`` is generated tokens a
second of the requests answered inside the window; ``units_since`` counts
prompt tokens prefilled plus tokens decoded (the program's
``vmt_prefill_chunk_fill`` and ``vmt_decode_batch_fill``, each sample a share
of its bucket), and ``flops_per_unit`` is 2 x the layers' matrix parameters
(head, attention and scan products left out: a lower bound, so a share of
the peak made from it cannot pass 100%). *A request yields one frame*, like
every task of the program: the tokens, each token's logit and the logits of
the ids asked for. *The traffic file's keys*: deck entries of
``prompt_tokens`` and ``count``; ``jitter`` (a prompt is its entry's length
less up to that share, so that no two are equal); ``max_new_tokens``;
``logit_ids`` (how many ids a request asks for). *The configuration's keys*:
the source ``config.json``'s keys at the top level, under their own names
(:func:`model_of`; the program's ``OlmoHybridConfig``), ``engine`` = the
program's ``GenerateConfig`` keys, ``reference`` the module under
``reference/``, ``weights`` the ranges the random weights are drawn in. *The check* is teacher-forced: the reference's
full forward runs over the prompt and the tokens that were *served*, so a
rounding flip of one arg-max cannot cascade; ``sample`` hands the served
tokens on with each picked request.

  assets, check_traffic   nothing on disk; the deck against the pool
  schedule                ``traffic.py`` (prompts, ids and logit ids from the seed)
  weights                 leaf by leaf in bfloat16, here
  boot                    ``server.py`` (``ServeApp`` around a ``GenerateEngine``)
  units_since, flops_per_unit, unwritten_bytes   here
  sample, run_reference, compare, frame_of       ``check.py``
"""

from __future__ import annotations

import functools
import importlib
import math
import time

from . import check, traffic

sample = check.sample
compare = check.compare
frame_of = check.frame_of


# The configuration file's own keys; every other top-level key is the source
# ``config.json``'s.
OWN_KEYS = frozenset({"name", "family", "source", "reference", "published",
                      "reduced", "deployment", "assumed", "weights",
                      "engine"})


def model_of(config: dict) -> dict:
    """The model's settings: the source's keys, which the file holds at its
    top level."""
    return {k: v for k, v in config.items() if k not in OWN_KEYS}


def _reference(config: dict):
    return importlib.import_module(
        f"benchmark.reference.{config['reference']}")


def check_traffic(config: dict, traffic_file: dict) -> None:
    """Raises where a traffic file cannot be run under a configuration: a
    prompt the context or the pool cannot hold, more callers than slots,
    or more logit ids than the engine returns."""
    engine, model = config["engine"], model_of(config)
    new = int(traffic_file["max_new_tokens"])
    longest = max(int(kind["prompt_tokens"]) for kind in traffic_file["deck"])
    if longest + new > model["max_position_embeddings"]:
        raise ValueError(f"a prompt of {longest} + {new} tokens is past the "
                         f"context of {model['max_position_embeddings']}")
    if -(-(longest + new) // engine["page_size"]) > engine["kv_pages"]:
        raise ValueError(f"a prompt of {longest} + {new} tokens needs more "
                         f"pages than the pool's {engine['kv_pages']}")
    if int(traffic_file["logit_ids"]) > engine["max_logit_ids"]:
        raise ValueError("the traffic asks for more logit ids than the "
                         f"engine returns ({engine['max_logit_ids']})")
    if max(engine["decode_buckets"]) < engine["slots"]:
        raise ValueError("the largest decode bucket must hold every slot")


def assets(config: dict, traffic_file: dict, cache_dir: str) -> tuple:
    """Nothing on disk: prompts are token ids drawn with the schedule."""
    t = time.monotonic()
    return ({"vocab_size": config["vocab_size"]},
            {"assets_s": time.monotonic() - t})


def schedule(traffic_file: dict, seed: int, seconds: float,
             assets: dict) -> dict:
    return traffic.schedule(traffic_file, seed, seconds,
                            assets["vocab_size"])


def weights(config: dict, seed: int) -> tuple:
    """(the served tree on the device, its parameter count). Drawn leaf by
    leaf in the stored type: the whole tree does not fit beside a float32
    copy of itself. The key is folded by leaf; the ranges are the
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

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def normal(k, shape, std):
        return (std * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    def log_uniform(k, shape, lo, hi):
        return jnp.exp(jax.random.uniform(
            k, shape, minval=math.log(lo), maxval=math.log(hi)))

    def draw(i, name, shape):
        k = jax.random.fold_in(key, i)
        if name.endswith("norm"):
            return (1.0 + normal(k, shape, drawn["norm_scale_std"])
                    ).astype(dtype)
        if name == "A_log":
            return jnp.log(log_uniform(k, shape, *drawn["A"])).astype(dtype)
        if name == "dt_bias":     # softplus^-1 of the drawn step
            return jnp.log(jnp.expm1(log_uniform(k, shape, *drawn["dt"]))
                           ).astype(dtype)
        return normal(k, shape, {
            "embed": drawn["embed_std"], "conv": drawn["conv_std"],
            "wa": drawn["wa_gain"] / math.sqrt(shape[-2]),
        }.get(name, 1.0 / math.sqrt(shape[-2])))

    out = [draw(i, path[-1].key, shape)
           for i, (path, shape) in enumerate(leaves)]
    count = sum(math.prod(shape) for _, shape in leaves)
    return jax.tree_util.tree_unflatten(treedef, out), count


def boot(config: dict, traffic_file: dict, params, assets: dict,
         state_dir: str, rehearsal: bool) -> tuple:
    from . import server

    return server.boot(server.framework_config(
        model_of(config), config["engine"], state_dir, rehearsal), params)


def _tokens_in(histogram: str, since: float) -> float:
    """Tokens dispatched since ``since``: each sample of the program's fill
    histogram is a share of its bucket."""
    from vilbert_multitask_tpu import obs

    fill = obs.REGISTRY.histogram(histogram, labelnames=("bucket",))
    return sum(
        sum(fill.window_samples(time.monotonic() - since, bucket=bucket))
        * float(bucket) for (bucket,) in fill.series_counts())


def units_since(app, since: float) -> float:
    return (_tokens_in("vmt_prefill_chunk_fill", since)
            + _tokens_in("vmt_decode_batch_fill", since))


def flops_per_unit(config: dict) -> int:
    """Matmul FLOPs of one token through the layers: 2 x the layers' matrix
    parameters. The head (once a sequence in prefill, once a token in
    decode), the attention products and the scan's are left out."""
    shapes = _reference(config).param_shapes(model_of(config))
    matrices = sum(
        math.prod(shape) for group in ("linear", "full")
        for name, shape in shapes[group].items()
        if name.startswith(("w", "mlp_")) and not name.endswith("norm"))
    return 2 * matrices


def unwritten_bytes(app, config: dict) -> tuple:
    """(bytes of sequence state reserved and never written to, what to say
    of them): slots no sequence ever ran in, pages no token was written
    to."""
    state = app.generate_engine.seqstate
    return state.unwritten_bytes(), {
        "seqstate_capacity_bytes": state.capacity_bytes,
        "slots": state.slots, "kv_pages": state.pages,
        "slots_written": len(state._slots_written),
        "pages_written": len(state._pages_written)}


def run_reference(config: dict, params, picked: list, assets: dict,
                  lower=None) -> list:
    return check.run_reference(model_of(config), _reference(config), params,
                               picked, lower=lower)
