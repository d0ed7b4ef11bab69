"""The ``olmo_hybrid`` family's requests: what a schedule's requests say.
When each is sent is ``harness/arrivals.py``'s, shared with every family.

A session is one document: one request of ``prompt_tokens`` token ids (its
deck entry's length less up to ``jitter`` of it, from the seed; no two of a
deck's worth of consecutive requests equal where the jitter has the room), ``max_new_tokens`` to generate and ``logit_ids`` ids whose logits
it wants at every generated position. The deck is dealt in a fixed order,
pass after pass, so every seed sends the same kinds in the same
proportions; the seed orders the entries *within* each pass, sets their
lengths and draws their ids uniformly over the vocabulary.
"""

from __future__ import annotations

import collections
import itertools

from ...harness import arrivals

GENERATE_TASK_ID = 20   # the program's ``generate`` task on ``POST /``


def schedule(traffic: dict, seed: int, seconds: float,
             vocab_size: int) -> dict:
    rng = arrivals.rng_for(seed, 1)
    made = itertools.count()
    # The lengths of the last deck's worth of requests: what is resident at
    # once holds no two equal prompts.
    recent: collections.deque = collections.deque(
        maxlen=sum(int(k["count"]) for k in traffic["deck"]))
    new = int(traffic["max_new_tokens"])
    n_ids = int(traffic["logit_ids"])

    def request(kind: dict) -> dict:
        nominal = int(kind["prompt_tokens"])
        room = max(1, int(nominal * float(traffic["jitter"])))
        for _ in range(16):
            length = nominal - int(rng.integers(0, room))
            if length not in recent:
                break
        recent.append(length)
        name = f"doc-{next(made)}"
        body = {"task_id": GENERATE_TASK_ID, "question": name,
                "prompt_ids": rng.integers(0, vocab_size, length).tolist(),
                "max_new_tokens": new,
                "logit_ids": rng.choice(vocab_size, size=n_ids,
                                        replace=False).tolist()}
        return {"body": body, "key": name, "key_field": "question",
                "rows": new, "kind": (nominal,),
                "deck_count": int(kind["count"])}

    def sessions(n: int) -> list:
        """The deck dealt pass after pass, each pass in the seed's order:
        with as many callers as the deck has entries, every round of
        requests (one a caller) is one deck, so what is resident at once
        and what a window holds is the deck's mix for every seed."""
        kinds = arrivals.deal(traffic["deck"], n)
        order = [start + int(pos)
                 for start in range(0, n, recent.maxlen)
                 for pos in rng.permutation(min(recent.maxlen, n - start))]
        return [[request(kinds[pos])
                 for _ in range(int(traffic["questions_per_session"]))]
                for pos in order]

    return arrivals.schedule(traffic, rng, seconds, sessions)
