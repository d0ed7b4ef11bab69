"""The ``vilbert`` family's schedules (``families/vilbert/traffic.py``) over
the shared arrival disciplines (``harness/arrivals.py``)."""

import hashlib
import json
import os

import pytest

from benchmark.families.vilbert import traffic
from benchmark.harness import arrivals
from benchmark.harness.spec import BENCH_DIR

WORDS = traffic.load_words(os.path.join(BENCH_DIR, "assets", "vocab.txt"))


def load(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seconds", [20.0, 51.0])
def test_every_seed_sends_the_same_multiset_in_another_order(seconds):
    mix = load("sessions")
    a = traffic.schedule(mix, 7, seconds, WORDS)["requests"]
    b = traffic.schedule(mix, 2**31 + 12345, seconds, WORDS)["requests"]
    assert arrivals.composition(a) == arrivals.composition(b)
    order = lambda rs: [(r["task_id"], len(r["images"]), r["source"])
                        for r in rs if r["due"] >= 0]
    assert order(a) != order(b)
    assert len({r["question"] for r in a}) == len(a)  # no duplicate submit


def test_window_requests_fall_inside_the_window_and_warm_before_it():
    mix = load("sessions")
    sched = traffic.schedule(mix, 3, 20.0, WORDS)
    dues = [r["due"] for r in sched["requests"]]
    assert min(dues) >= -mix["warm_seconds"] and max(dues) < 20.0
    assert dues == sorted(dues)
    timed = [r for r in sched["requests"] if r["due"] >= 0]
    assert len(timed) == round(mix["requests_per_s"] * 20.0 / 4) * 4


def test_an_upload_is_asked_about_by_one_session_only():
    sched = traffic.schedule(load("sessions"), 11, 30.0, WORDS)
    owner = {}
    for r in sched["requests"]:
        if r["source"] == "upload":
            for name in r["images"]:
                assert owner.setdefault(name, r["session"]) == r["session"]


def test_the_deck_is_dealt_in_its_shares():
    mix = load("sessions")
    kinds = arrivals.deal(mix["deck"], 700)
    share = lambda pred: sum(1 for k in kinds if pred(k)) / len(kinds)
    assert share(lambda k: k["task_id"] == 7) == pytest.approx(0.2)
    assert share(lambda k: k["task_id"] == 12) == pytest.approx(0.2)
    assert share(lambda k: k["source"] == "upload") == pytest.approx(0.4)


def test_a_closed_loop_gives_every_client_a_list_that_outlasts_the_run():
    mix = load("backlog")
    sched = traffic.schedule(mix, 5, 30.0, WORDS)
    per_client = {}
    for r in sched["requests"]:
        per_client[r["client"]] = per_client.get(r["client"], 0) + 1
        assert len(r["images"]) == 10 and r["task_id"] == 7
    assert len(per_client) == mix["clients"]
    assert min(per_client.values()) * mix["clients"] >= \
        mix["max_requests_per_s"] * 30.0


@pytest.mark.parametrize("mix", ["sessions", "backlog"])
def test_the_gallery_set_up_holds_is_the_one_requests_draw_from(mix):
    mix = load(mix)
    held = traffic.gallery(mix)
    assert len(held) == len(set(held)) == mix["gallery_images"]
    sched = traffic.schedule(mix, 3, 20.0, WORDS)
    drawn = {n for r in sched["requests"] if r["source"] == "gallery"
             for n in r["images"]}
    assert drawn and drawn <= set(held)


with open(os.path.join(BENCH_DIR, "tests", "data",
                       "schedule_digests.json")) as f:
    DIGESTS = json.load(f)


@pytest.mark.parametrize("name", sorted(DIGESTS["digests"]))
def test_a_seeds_schedule_is_the_parents_field_for_field(name):
    """``data/schedule_digests.json`` was written by ``harness/traffic.py``
    on the tree before the family seam (PR 27's parent): the same seed
    still sends the same requests at the same times."""
    mix, seed = name.split(":")
    sched = traffic.schedule(load(mix), int(seed), DIGESTS["seconds"], WORDS)
    rows = [[r.get(k) for k in DIGESTS["fields"]] for r in sched["requests"]]
    want = DIGESTS["digests"][name]
    assert len(rows) == want["requests"]
    assert hashlib.sha256(json.dumps(rows, sort_keys=True).encode()
                          ).hexdigest() == want["sha256"]


def test_a_request_says_what_the_generator_and_the_window_need():
    sched = traffic.schedule(load("sessions"), 9, 10.0, WORDS)
    for r in sched["requests"]:
        assert r["body"] == {"task_id": r["task_id"],
                             "question": r["question"],
                             "image_list": r["images"]}
        assert (r["key_field"], r["key"]) == ("question",
                                              r["question"].lower())
        assert r["rows"] == len(r["images"])
        assert r["kind"] == (r["task_id"], len(r["images"]), r["source"])
