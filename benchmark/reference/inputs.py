"""Plain host-side input preparation for the reference: a question and the
feature files of its images become the arrays the model reads.

Written from the served deployment's description, not from the program:
BERT's uncased WordPiece over the deployment's vocabulary file, [CLS] and
[SEP] around it, zero padding appended up to 37 tokens; per image the mean
region feature prepended as the whole-image region, boxes normalised to
[x1/w, y1/h, x2/w, y2/h, area share], everything padded to 101 regions.
"""

from __future__ import annotations

import string

import numpy as np

GLOBAL_BOX = (0.0, 0.0, 1.0, 1.0, 1.0)
GUESSWHAT_TASK = 16


def load_vocab(path: str) -> dict:
    vocab = {}
    with open(path, encoding="utf-8") as f:
        for idx, line in enumerate(f):
            token = line.rstrip("\n")
            if token:
                vocab[token] = idx
    return vocab


def _split_punctuation(word: str) -> list:
    pieces, current = [], ""
    for ch in word:
        if ch in string.punctuation:
            if current:
                pieces.append(current)
            pieces.append(ch)
            current = ""
        else:
            current += ch
    if current:
        pieces.append(current)
    return pieces


def _wordpiece(word: str, vocab: dict) -> list:
    pieces, start = [], 0
    while start < len(word):
        end = len(word)
        while end > start:
            sub = ("##" if start else "") + word[start:end]
            if sub in vocab:
                pieces.append(sub)
                break
            end -= 1
        else:
            return ["[UNK]"]
        start = end
    return pieces


def _guesswhat_dialog(question: str) -> str:
    """``q: .. a: ..`` turns become ``start .. answer .. stop``."""
    turns = question.split("q:")[1:]
    if not turns:
        return question
    parts = []
    for turn in turns:
        qa = turn.split("a:")
        answer = qa[1].strip() if len(qa) > 1 else ""
        parts.append(f"start {qa[0].strip()} answer {answer} stop")
    return " ".join(parts)


def encode_question(question: str, task_id: int, vocab: dict,
                    max_len: int) -> tuple:
    """ASCII questions only (what the traffic generator words)."""
    question = question.lower()
    if task_id == GUESSWHAT_TASK:
        question = _guesswhat_dialog(question)
    tokens = []
    for word in question.split():
        for piece in _split_punctuation(word):
            tokens.extend(_wordpiece(piece, vocab))
    ids = [vocab["[CLS]"]] + [vocab.get(t, vocab["[UNK]"]) for t in tokens]
    ids.append(vocab["[SEP]"])
    if len(ids) > max_len:
        ids = ids[:max_len - 1] + [vocab["[SEP]"]]
    input_ids = np.zeros((max_len,), np.int32)
    input_ids[:len(ids)] = ids
    input_mask = np.zeros((max_len,), np.int32)
    input_mask[:len(ids)] = 1
    return input_ids, input_mask


def encode_image(path: str, max_regions: int) -> dict:
    """One reference-schema ``.npy`` feature file → padded region arrays."""
    raw = np.load(path, allow_pickle=True).item()
    n = min(int(raw["num_boxes"]), max_regions - 1)
    feats = np.asarray(raw["features"], np.float32)[:n]
    boxes = np.asarray(raw["bbox"], np.float32)[:n]
    w, h = float(raw["image_width"]), float(raw["image_height"])
    features = np.zeros((max_regions, feats.shape[1]), np.float32)
    features[0] = feats.sum(axis=0) / max(n, 1)
    features[1:n + 1] = feats
    spatials = np.zeros((max_regions, 5), np.float32)
    spatials[0] = GLOBAL_BOX
    spatials[1:n + 1, 0] = boxes[:, 0] / w
    spatials[1:n + 1, 1] = boxes[:, 1] / h
    spatials[1:n + 1, 2] = boxes[:, 2] / w
    spatials[1:n + 1, 3] = boxes[:, 3] / h
    spatials[1:n + 1, 4] = ((boxes[:, 3] - boxes[:, 1])
                            * (boxes[:, 2] - boxes[:, 0])) / (w * h)
    mask = np.zeros((max_regions,), np.int32)
    mask[:n + 1] = 1
    return {"features": features, "spatials": spatials, "image_mask": mask}


def request_batch(question: str, task_id: int, feature_paths: list,
                  vocab: dict, max_len: int, max_regions: int,
                  rows: int) -> dict:
    """The fixed-shape batch of one request: one row per image, padded to
    ``rows`` with empty rows (a single attended whole-image region)."""
    ids, mask = encode_question(question, task_id, vocab, max_len)
    images = [encode_image(p, max_regions) for p in feature_paths]
    dim = images[0]["features"].shape[1]
    batch = {
        "input_ids": np.tile(ids, (rows, 1)),
        "input_mask": np.tile(mask, (rows, 1)),
        "features": np.zeros((rows, max_regions, dim), np.float32),
        "spatials": np.zeros((rows, max_regions, 5), np.float32),
        "image_mask": np.zeros((rows, max_regions), np.int32),
        "task_ids": np.full((rows,), task_id, np.int32),
    }
    batch["spatials"][:, 0] = GLOBAL_BOX
    batch["image_mask"][:, 0] = 1
    for i, img in enumerate(images):
        for key, value in img.items():
            batch[key][i] = value
    return batch
