"""The comparison that decides ``correct`` in an ``olmo_hybrid`` cell.

For each sampled request the plain reference runs its full causal forward
over the prompt and the tokens that were *served* (teacher-forced: a
rounding flip of one arg-max then moves one position's choice, not every
later one), and every logit the frame carries (each generated token's, and
at every generated position the ids the request asked for) is compared with
the reference's for the same id at the same position, each difference
divided by the spread (standard deviation over the vocabulary) of the
reference's logits at that position:

  logit_err_rms   root mean square of the scaled differences
  logit_err_max   the largest of them
  argmax_gap_max  the largest amount by which the served token's reference
                  logit lies under the reference's best, scaled alike

That covers door -> queue -> scheduler -> chunked prefill through the scan
kernel and the pages -> every decode step through the state manager ->
frame. ``unanswered``: sampled frames missing or malformed.
"""

from __future__ import annotations

import numpy as np

from ...harness import arrivals


def sample(requests: list, stamps: dict, seed: int, limit: int) -> list:
    """Answered window requests drawn from the seed, of every kind as many
    as its deck entry counts, the longest kind first, ``limit`` at most;
    each with the tokens that were served for it."""
    answered = [r for r in requests if r["i"] in stamps]
    arrivals.rng_for(seed, 2).shuffle(answered)
    by_kind: dict = {}
    for r in answered:
        by_kind.setdefault(tuple(r["kind"]), []).append(r)
    picked = []
    for kind in sorted(by_kind, reverse=True):
        mine = by_kind[kind]
        picked += mine[:mine[0]["deck_count"]]
    return [dict(r, served_tokens=list(
        (stamps[r["i"]].get("result") or {}).get("tokens") or []))
        for r in picked[:limit]]


def run_reference(model: dict, reference, params, picked: list,
                  lower=None) -> list:
    """Per request, at each generated position: the reference's logit of
    the served token (``chosen``), of the ids asked for (``ids``), its best
    logit and the spread of its logits."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def reduce(logits, tokens, ids):
        rows = jnp.arange(logits.shape[0])
        return {"chosen": logits[rows, tokens], "ids": logits[:, ids],
                "best": logits.max(-1), "spread": logits.std(-1)}

    outputs = []
    with jax.default_matmul_precision("highest"):
        for r in picked:
            prompt = r["body"]["prompt_ids"]
            tokens = r["served_tokens"]
            if len(tokens) != r["body"]["max_new_tokens"]:
                outputs.append(None)      # nothing to force: unanswered
                continue
            rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
            logits = reference.forward(params, model,
                                       prompt + tokens[:-1], lower=lower,
                                       rows=rows)
            outputs.append({k: np.asarray(v, np.float64) for k, v in reduce(
                logits, jnp.asarray(tokens),
                jnp.asarray(r["body"]["logit_ids"])).items()})
    return outputs


def frame_of(request: dict, output) -> dict:
    """What a frame would say had ``output`` been served (the control's
    lower-precision reference in the program's place)."""
    if output is None:
        return {}
    return {"question": request["key"], "tokens": request["served_tokens"],
            "token_logits": output["chosen"].tolist(),
            "logits": output["ids"].tolist()}


def compare(picked: list, stamps: dict, outputs: list) -> dict:
    errors, gaps, unanswered = [], [], 0
    for request, ref in zip(picked, outputs):
        result = (stamps.get(request["i"]) or {}).get("result") or {}
        chosen = np.asarray(result.get("token_logits", []), np.float64)
        ids = np.asarray(result.get("logits", []), np.float64)
        if (ref is None or result.get("question") != request["key"]
                or result.get("tokens") != request["served_tokens"]
                or chosen.shape != ref["chosen"].shape
                or ids.shape != ref["ids"].shape):
            unanswered += 1
            continue
        spread = ref["spread"]
        errors.append(((chosen - ref["chosen"]) / spread).ravel())
        errors.append(((ids - ref["ids"]) / spread[:, None]).ravel())
        gaps.append(((ref["best"] - ref["chosen"]) / spread).max())
    if not errors:
        return {"logit_err_rms": float("inf"), "logit_err_max": float("inf"),
                "argmax_gap_max": float("inf"), "unanswered": unanswered,
                "compared": len(picked)}
    err = np.concatenate(errors)
    kinds: dict = {}
    for request in picked:
        kinds[request["kind"][0]] = kinds.get(request["kind"][0], 0) + 1
    return {"logit_err_rms": float(np.sqrt(np.mean(err ** 2))),
            "logit_err_max": float(np.abs(err).max()),
            "argmax_gap_max": float(max(gaps)),
            "unanswered": unanswered, "compared": len(picked),
            "logits_compared": int(err.size),
            "kinds_compared": {str(k): v for k, v in sorted(kinds.items())}}
