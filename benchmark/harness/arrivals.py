"""The arrival disciplines every family's schedule shares: a traffic file of
parameters, a seed's generator and a window length place a family's
*sessions* in time. What a session's requests say is the family's business
(``families/<family>``: ``schedule``); when they are sent, and by whom, is
decided here, the same way for every family.

A *session* is one user sending ``questions_per_session`` requests. The
traffic file's ``deck`` lists the kinds of session with whole-number
``count``s; the deck is dealt in a fixed interleaved order (:func:`deal`),
as many sessions as the rate and the window need, so **the multiset of
requests in the window is the same for every seed**. The seed only orders
the sessions, times them and draws what the family draws.

``arrivals: "open"``   sessions start at seed-drawn times on the window taken
                       as a circle (a session that would run past the end
                       wraps to the start, as the tail of a session that
                       began before the window would); its requests follow
                       ``think_time_s`` apart. Every request has a ``due``.
``arrivals: "closed"`` ``clients`` callers each work through their own list
                       of sessions, sending the next request the moment the
                       last is answered. Requests have a ``client`` and no
                       due time.

The warm phase (``warm_seconds`` of the same traffic before the window,
untimed) is generated the same way with due times below zero.
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, *tags: int) -> np.random.Generator:
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


def _open_phase(traffic, rng, start: float, length: float, make_sessions,
                first_session: int) -> list:
    q = int(traffic["questions_per_session"])
    n = max(1, round(traffic["requests_per_s"] * length / q))
    lo, hi = traffic["think_time_s"]
    requests = []
    for s, session in enumerate(make_sessions(n)):
        t = float(rng.random()) * length
        for request in session:
            requests.append(dict(request, due=start + (t % length),
                                 session=first_session + s))
            t += float(rng.uniform(lo, hi))
    return requests


def schedule(traffic: dict, rng, seconds: float, make_sessions) -> dict:
    """The requests of one run: the warm phase and the window.
    ``make_sessions(n)`` is the family's: ``n`` sessions in the seed's
    order, each a list of its requests (``body``, ``key``, ``key_field``,
    ``rows``, ``kind`` and whatever the family's check wants to find
    again), drawn from the same ``rng`` that times them here. This adds
    ``due`` or ``client``, ``session`` and ``i``."""
    warm = float(traffic["warm_seconds"])
    if traffic["arrivals"] == "open":
        requests = _open_phase(traffic, rng, -warm, warm, make_sessions, 0)
        n_warm = len(requests)
        requests += _open_phase(traffic, rng, 0.0, float(seconds),
                                make_sessions, n_warm)
        requests.sort(key=lambda r: r["due"])
    elif traffic["arrivals"] == "closed":
        clients = int(traffic["clients"])
        q = int(traffic["questions_per_session"])
        # Enough for every client never to run dry: the file bounds the
        # rate any system could answer at.
        per_client = max(1, int(np.ceil(
            traffic["max_requests_per_s"] * (seconds + warm) / clients / q)))
        requests = [dict(request, client=s % clients, session=s)
                    for s, session in enumerate(
                        make_sessions(clients * per_client))
                    for request in session]
    else:
        raise SystemExit(f"unknown arrivals {traffic['arrivals']!r}")
    for i, r in enumerate(requests):
        r["i"] = i
    return {"arrivals": traffic["arrivals"], "warm_seconds": warm,
            "seconds": float(seconds), "requests": requests}


def composition(requests: list) -> list:
    """The sorted multiset of ``kind`` over the timed requests of an
    open-loop schedule: what no seed may change."""
    return sorted(tuple(r["kind"]) for r in requests
                  if r.get("due", 0.0) >= 0.0)
