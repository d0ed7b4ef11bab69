"""How ``correct`` is decided: the frames the timed window returned, against
the plain float32 reference run over the same questions and feature files.

Every score a frame carries is turned back into the logit it came from (a
confidence is a softmax, so its log is the logit less a per-row constant)
and compared with the reference's logit for the same label, class, image or
region. Each difference is divided by the spread of the reference's logits
for that head over the sample, so heads of different scale weigh alike.
Numbers compared, each with its limit (``benchmark/limits/<cell>.json``):

  score_err_rms   root mean square of the scaled differences
  score_err_max   the largest of them, with for the two top-k families the
                  gap by which the served best lies below the reference's best
  unanswered      sampled requests whose frame is missing, malformed or
                  names something the request did not hold (limit 0)
"""

from __future__ import annotations

import math

import numpy as np

from ...reference import inputs
from . import catalog

LABEL_TASKS = {1: "vqa", 2: "vqa", 15: "gqa"}
GROUNDING_TASKS = (4, 11, 16)
BINARY_LABELS = ("False", "True")
TRINARY_LABELS = ("contradiction (false)", "neutral", "entailment (true)")
REFERENCE_ROWS = 10  # the widest request; one compiled reference program


def sample(requests: list, stamps: dict, seed: int, limit: int) -> list:
    """Answered window requests to compare: drawn from the seed, spread over
    every (task, image count, source) the window held, the widest first."""
    rng = np.random.default_rng([int(seed), 2])
    groups: dict = {}
    for r in requests:
        if r["i"] in stamps:
            key = (r["task_id"], len(r["images"]), r["source"])
            groups.setdefault(key, []).append(r)
    for members in groups.values():
        rng.shuffle(members)
    order = sorted(groups, key=lambda k: (-k[1], k))
    picked = []
    while len(picked) < limit and any(groups.values()):
        for key in order:
            if groups[key] and len(picked) < limit:
                picked.append(groups[key].pop())
    return picked


def _log(p: float) -> float:
    return math.log(max(float(p), 1e-300))


def _log_softmax(logits) -> np.ndarray:
    logits = np.asarray(logits, np.float64)
    top = logits.max()
    return logits - (np.log(np.exp(logits - top).sum()) + top)


def _pairs(request: dict, result: dict, ref: dict) -> tuple:
    """(head, [(served logit-like, reference logit-like)], [gaps]) for one
    answered request; raises ValueError on a malformed frame."""
    task = request["task_id"]
    n = len(request["images"])
    if int(result.get("task_id", -1)) != task:
        raise ValueError("frame of another task")
    if task in LABEL_TASKS:
        head = LABEL_TASKS[task]
        logp = _log_softmax(ref[head][0])
        answers = result["answers"]
        if len(answers) != 3:
            raise ValueError("expected 3 answers")
        idx = [int(a["answer"]) for a in answers]
        pairs = [(_log(a["confidence"]), logp[i])
                 for a, i in zip(answers, idx)]
        return head, pairs, [logp.max() - logp[idx[0]]]
    if task == 12 or task == 13:
        head, labels = (("binary", BINARY_LABELS) if task == 12
                        else ("trinary", TRINARY_LABELS))
        logp = _log_softmax(ref[head] if task == 12 else ref[head][0])
        answers = result["answers"]
        if sorted(a["answer"] for a in answers) != sorted(labels):
            raise ValueError("wrong class names")
        return head, [(_log(a["confidence"]), logp[labels.index(a["answer"])])
                      for a in answers], []
    if task == 7:
        scores = np.asarray(ref["ranking"][:n], np.float64)
        ranking = result["ranking"]
        if sorted(e["image"] for e in ranking) != sorted(request["images"]):
            raise ValueError("ranking names other images")
        return "ranking", [(float(e["score"]),
                            scores[request["images"].index(e["image"])])
                           for e in ranking], []
    if task in GROUNDING_TASKS:
        logits = np.asarray(ref["grounding"][0], np.float64)
        boxes = result["boxes"]
        if len(boxes) != 3:
            raise ValueError("expected 3 boxes")
        idx = [int(b["region_index"]) for b in boxes]
        if not all(0 <= i < logits.shape[0] for i in idx):
            raise ValueError("region index out of range")
        return "grounding", [(float(b["score"]), logits[i])
                             for b, i in zip(boxes, idx)], [
            logits.max() - logits[idx[0]]]
    raise ValueError(f"task {task} is not one the check knows")


def frame_of(request: dict, out: dict) -> dict:
    """What a frame would say had ``out`` (a forward's head outputs) been
    served: the fields :func:`_pairs` reads, decoded plainly. This is how
    the control (the reference in a lower precision) takes the program's
    place in the comparison."""
    task, n = request["task_id"], len(request["images"])

    def softmax(logits):
        e = np.exp(logits - logits.max())
        return e / e.sum()

    if task in LABEL_TASKS:
        logits = np.asarray(out[LABEL_TASKS[task]][0], np.float64)
        conf = softmax(logits)
        top = np.argsort(-logits)[:3]
        body = {"answers": [{"answer": str(int(i)),
                             "confidence": float(conf[i])} for i in top]}
    elif task == 12 or task == 13:
        labels = BINARY_LABELS if task == 12 else TRINARY_LABELS
        logits = np.asarray(out["binary"] if task == 12
                            else out["trinary"][0], np.float64)
        body = {"answers": [{"answer": name, "confidence": float(c)}
                            for name, c in zip(labels, softmax(logits))]}
    elif task == 7:
        body = {"ranking": [{"image": name, "score": float(score)}
                            for name, score in zip(request["images"],
                                                   out["ranking"][:n])]}
    else:
        logits = np.asarray(out["grounding"][0], np.float64)
        body = {"boxes": [{"region_index": int(i), "score": float(logits[i])}
                          for i in np.argsort(-logits)[:3]]}
    return dict(body, task_id=task)


def _spread(head: str, ref: dict, n: int) -> np.ndarray:
    """The reference logits whose spread scales a head's differences."""
    if head in ("vqa", "gqa", "trinary"):
        return np.asarray(ref[head][0], np.float64)
    if head == "binary":
        return np.asarray(ref[head], np.float64)
    if head == "ranking":
        return np.asarray(ref[head][:n], np.float64)
    logits = np.asarray(ref["grounding"][0], np.float64)
    return logits[logits > -5000.0]  # regions inside the image mask


def compare(picked: list, stamps: dict, references: list) -> dict:
    """The numbers of one run. ``references[k]`` is the reference's output
    for ``picked[k]``."""
    by_head: dict = {}
    unanswered = 0
    for request, ref in zip(picked, references):
        result = stamps[request["i"]].get("result")
        try:
            if result is None:
                raise ValueError("no frame")
            head, pairs, gaps = _pairs(request, result, ref)
        except (ValueError, KeyError, TypeError):
            unanswered += 1
            continue
        slot = by_head.setdefault(head, {"diffs": [], "spread": []})
        slot["diffs"] += [abs(a - b) for a, b in pairs] + list(gaps)
        slot["spread"].append(_spread(head, ref, len(request["images"])))
    scaled = []
    per_head, per_head_scale = {}, {}
    for head, slot in by_head.items():
        scale = float(np.concatenate(slot["spread"]).std())
        values = np.asarray(slot["diffs"], np.float64) / max(scale, 1e-12)
        per_head[head] = float(np.sqrt((values ** 2).mean()))
        per_head_scale[head] = scale
        scaled.append(values)
    if scaled:
        values = np.concatenate(scaled)
        rms, worst = float(np.sqrt((values ** 2).mean())), float(values.max())
    else:
        rms = worst = float("inf")
    return {"score_err_rms": rms, "score_err_max": worst,
            "unanswered": unanswered, "compared": len(picked),
            "per_head_rms": per_head, "per_head_scale": per_head_scale}


def run_reference(config: dict, module, params: dict, picked: list,
                  feature_root: str, vocab_path: str, lower=None) -> list:
    """The head outputs of the reference (``module``: the configuration's
    file under ``reference/``) for each picked request, one request to a
    call, all through one compiled float32 program (``lower``: with the
    dense layers' operands rounded to that precision, for the control)."""
    import jax

    model, engine = config["model"], config["engine"]
    vocab = inputs.load_vocab(vocab_path)
    fwd = jax.jit(lambda p, b: module.forward(p, model, b, lower=lower))
    out = []
    with jax.default_matmul_precision("highest"):
        for r in picked:
            batch = inputs.request_batch(
                r["question"], r["task_id"],
                [catalog.feature_path(feature_root, name)
                 for name in r["images"]],
                vocab, engine["max_text_len"], engine["max_regions"],
                REFERENCE_ROWS)
            out.append(jax.device_get(fwd(params, batch)))
    return out
