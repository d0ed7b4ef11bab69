"""ViLBERT's sessions: what the requests of a schedule say. A traffic file
of parameters plus a seed and a window length give the schedule of requests
a run sends; when each is sent is ``harness/arrivals.py``'s, shared with
every family.

A *session* is one user with one image set asking ``questions_per_session``
questions. The traffic file's ``deck`` lists the kinds of session (task,
image count, gallery image or the user's own upload) with whole-number
counts; the deck is dealt in a fixed interleaved order, so **the multiset of
requests in the window is the same for every seed**. The seed only orders
the sessions, times them, picks their images and words their questions.
"""

from __future__ import annotations

from ...harness import arrivals

GUESSWHAT_TASK = 16


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
    """``n`` dealt sessions in seed order, each the list of its requests
    (one image set, its questions); ``uploads`` is the seed's order of the
    upload pool."""
    kinds = arrivals.deal(traffic["deck"], n)
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
        out.append([_request(int(kind["task_id"]), images, kind["source"],
                             question) for question in questions])
    return out


def _request(task_id: int, images: list, source: str, question: str) -> dict:
    """One request as the generic code wants it (``body`` is POSTed as it
    is; the frame whose ``result[key_field]`` equals ``key`` answers it: the
    server lower-cases questions, and frames carry them that way; ``rows``
    image rows of work; ``kind`` is what no seed changes the count of),
    with what the check reads beside it."""
    return {"task_id": task_id, "question": question, "images": images,
            "source": source,
            "body": {"task_id": task_id, "question": question,
                     "image_list": images},
            "key": question.lower(), "key_field": "question",
            "rows": len(images), "kind": (task_id, len(images), source)}


def schedule(traffic: dict, seed: int, seconds: float, words: list) -> dict:
    """The requests of one run: the warm phase and the window."""
    rng = arrivals.rng_for(seed, 1)
    wording = _Wording(rng, words, traffic["question_words"])
    uploads = [int(u) for u in rng.permutation(int(traffic["upload_pool"]))]
    return arrivals.schedule(
        traffic, rng, seconds,
        lambda n: _sessions(traffic, rng, n, uploads, wording))


def gallery(traffic: dict) -> list:
    """The images a deployment holds on the device before anything is
    timed, in the order in which set-up puts them there."""
    return [f"g{g:04d}.jpg" for g in range(int(traffic["gallery_images"]))]
