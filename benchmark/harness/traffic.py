"""The one traffic generator: a traffic file of parameters plus a seed and a
window length give the schedule of requests a run sends.

A *session* is one user with one image set asking ``questions_per_session``
questions. The traffic file's ``deck`` lists the kinds of session (task,
image count, gallery image or the user's own upload) with whole-number
counts; the deck is dealt in a fixed interleaved order, as many sessions as
the rate and the window need, so **the multiset of requests in the window is
the same for every seed**. The seed only orders the sessions, times them,
picks their images and words their questions.

``arrivals: "open"``   sessions start at seed-drawn times on the window taken
                       as a circle (a session that would run past the end
                       wraps to the start, as the tail of a session that
                       began before the window would); questions follow
                       ``think_time_s`` apart. Every request has a due time.
``arrivals: "closed"`` ``clients`` callers each work through their own list
                       of sessions, sending the next question the moment the
                       last is answered. Requests have no due time.

The warm phase (``warm_seconds`` of the same traffic before the window,
untimed) is generated the same way with due times below zero.
"""

from __future__ import annotations

import numpy as np

GUESSWHAT_TASK = 16


def _rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *tags])


def deal(deck: list, n: int) -> list:
    """The first ``n`` sessions of the deck dealt round-robin over its
    entries (each entry as often as its ``count``), cyclically."""
    left = [int(e["count"]) for e in deck]
    one_pass = []
    while any(left):
        for i, entry in enumerate(deck):
            if left[i]:
                left[i] -= 1
                one_pass.append(entry)
    return [one_pass[i % len(one_pass)] for i in range(n)]


def load_words(vocab_path: str) -> list:
    """Whole alphabetic words of the deployment's vocabulary."""
    with open(vocab_path, encoding="utf-8") as f:
        tokens = [line.rstrip("\n") for line in f]
    return [t for t in tokens if t.isascii() and t.isalpha() and len(t) > 2]


class _Wording:
    """Distinct questions, so no request is another's duplicate (the result
    cache and coalescing stay out of these cells)."""

    def __init__(self, rng, words: list, length: list):
        self.rng, self.words, self.length = rng, words, length
        self.seen: set = set()

    def _phrase(self, lo: int, hi: int) -> str:
        n = int(self.rng.integers(lo, hi + 1))
        return " ".join(self.words[i] for i in
                        self.rng.integers(0, len(self.words), n))

    def question(self, task_id: int) -> str:
        lo, hi = self.length
        while True:
            if task_id == GUESSWHAT_TASK:
                turns = int(self.rng.integers(1, 4))
                text = " ".join(
                    f"q: is it {self._phrase(1, 3)}? a: "
                    f"{'yes' if self.rng.random() < 0.5 else 'no'}"
                    for _ in range(turns))
            else:
                text = self._phrase(lo, hi)
            if text not in self.seen:
                self.seen.add(text)
                return text


def _sessions(traffic: dict, rng, n: int, uploads: list, wording) -> list:
    """``n`` dealt sessions in seed order, each with its images and its
    questions; ``uploads`` is the seed's order of the upload pool."""
    kinds = deal(traffic["deck"], n)
    order = rng.permutation(n)
    out = []
    for pos in order:
        kind = kinds[pos]
        k = int(kind["images"])
        if kind["source"] == "upload":
            if len(uploads) < k:
                raise SystemExit("traffic needs more uploads than "
                                 "upload_pool holds; raise it")
            images = [f"u{uploads.pop():05d}.jpg" for _ in range(k)]
        else:
            picks = rng.choice(traffic["gallery_images"], size=k,
                               replace=False)
            images = [f"g{int(g):04d}.jpg" for g in picks]
        questions = [wording.question(int(kind["task_id"]))
                     for _ in range(int(traffic["questions_per_session"]))]
        out.append({"task_id": int(kind["task_id"]), "images": images,
                    "source": kind["source"], "questions": questions})
    return out


def _open_phase(traffic, rng, start: float, length: float, uploads,
                wording, first_session: int) -> list:
    q = int(traffic["questions_per_session"])
    n = max(1, round(traffic["requests_per_s"] * length / q))
    lo, hi = traffic["think_time_s"]
    requests = []
    for s, session in enumerate(_sessions(traffic, rng, n, uploads, wording)):
        t = float(rng.random()) * length
        for question in session["questions"]:
            requests.append({
                "due": start + (t % length), "task_id": session["task_id"],
                "question": question, "images": session["images"],
                "source": session["source"], "session": first_session + s})
            t += float(rng.uniform(lo, hi))
    return requests


def schedule(traffic: dict, seed: int, seconds: float, words: list) -> dict:
    """The requests of one run: the warm phase and the window."""
    rng = _rng(seed, 1)
    wording = _Wording(rng, words, traffic["question_words"])
    uploads = [int(u) for u in rng.permutation(int(traffic["upload_pool"]))]
    warm = float(traffic["warm_seconds"])
    if traffic["arrivals"] == "open":
        requests = _open_phase(traffic, rng, -warm, warm, uploads, wording, 0)
        n_warm = len(requests)
        requests += _open_phase(traffic, rng, 0.0, float(seconds), uploads,
                                wording, n_warm)
        requests.sort(key=lambda r: r["due"])
    elif traffic["arrivals"] == "closed":
        clients = int(traffic["clients"])
        q = int(traffic["questions_per_session"])
        # Enough for every client never to run dry: the file bounds the
        # rate any system could answer at.
        per_client = max(1, int(np.ceil(
            traffic["max_requests_per_s"] * (seconds + warm) / clients / q)))
        sessions = _sessions(traffic, rng, clients * per_client, uploads,
                             wording)
        requests = []
        for s, session in enumerate(sessions):
            for question in session["questions"]:
                requests.append({
                    "client": s % clients, "task_id": session["task_id"],
                    "question": question, "images": session["images"],
                    "source": session["source"], "session": s})
    else:
        raise SystemExit(f"unknown arrivals {traffic['arrivals']!r}")
    for i, r in enumerate(requests):
        r["i"] = i
    return {"arrivals": traffic["arrivals"], "warm_seconds": warm,
            "seconds": float(seconds), "requests": requests}


def gallery(traffic: dict) -> list:
    """The images a deployment holds on the device before anything is
    timed, in the order in which set-up puts them there."""
    return [f"g{g:04d}.jpg" for g in range(int(traffic["gallery_images"]))]


def composition(requests: list) -> list:
    """The sorted multiset of (task, image count, source) over the timed
    requests of an open-loop schedule."""
    return sorted((r["task_id"], len(r["images"]), r["source"])
                  for r in requests if r.get("due", 0.0) >= 0.0)
