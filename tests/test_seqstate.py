"""The sequence-state manager's accounting (ISSUE 28, test e): admit,
release, refuse by slots, pages and bytes; pages never shared; bytes in use
equal to the sum over live sequences; nothing leaked."""

import dataclasses

import numpy as np
import pytest

from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.config import (
    GenerateConfig,
    LagunaConfig,
    OlmoHybridConfig,
    Phi4FlashConfig,
)
from vilbert_multitask_tpu.engine import seqstate
from vilbert_multitask_tpu.engine.generate import model_module

GEN = GenerateConfig(model=OlmoHybridConfig().tiny(), param_dtype="float32",
                     slots=4, kv_pages=32, page_size=16,
                     decode_attention_pages=4)
# The same accounting under the other model's layout (ISSUE 32): a slot of
# rings, pages of 2 full layers of 2 key/value heads.
LAGUNA = dataclasses.replace(GEN, model=LagunaConfig().tiny())
# And under the third's (ISSUE 34): a slot of Mamba states, convolution
# rows and rings, pages of one layer whatever the depth.
PHI4FLASH = dataclasses.replace(GEN, model=Phi4FlashConfig().tiny())
BOTH = pytest.mark.parametrize("gen", [GEN, LAGUNA, PHI4FLASH],
                               ids=["olmo_hybrid", "laguna", "phi4flash"])


def SequenceState(gen):
    """The manager as the engine builds it: the layout from the model's
    module."""
    return seqstate.SequenceState(gen, model_module(gen.model).state_layout(
        gen.model, gen.param_dtype))


def counter(name, **labels):
    inst = obs.REGISTRY.counter(name, labelnames=tuple(labels))
    return inst.collect().get(tuple(labels.values()), 0.0)


def test_sizes_follow_the_model():
    st = SequenceState(GEN)
    m = GEN.model
    # 6 linear layers: [4, 8, 16] float32 + 3 rows of 4 * (8 + 8 + 16)
    assert st.slot_bytes == 6 * (4 * 8 * 16 * 4 + 3 * 128 * 4)
    # 2 full layers, K and V, 16 tokens of 4 heads of 16
    assert st.page_bytes == 2 * 2 * 16 * 4 * 16 * 4
    assert st.capacity_bytes == 4 * st.slot_bytes + 32 * st.page_bytes
    assert st.slot_shapes == {"rec": (2, 3, 4, 4, 8, 16),
                              "conv": (2, 3, 4, 3, 128)}
    assert st.ring_bytes == 0
    assert st.pool_shape == (2, 33, 4, 16, 16)   # one page is nobody's
    assert st.max_pages_per_seq == m.max_position_embeddings // 16
    full = SequenceState(GenerateConfig(model=dataclasses.replace(
        OlmoHybridConfig(), num_hidden_layers=16,
        layer_types=OlmoHybridConfig().layer_types[:16])))
    assert full.slot_bytes == 12 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    assert full.page_bytes == 4 * 2 * 256 * 30 * 128 * 2
    # to the byte what the served size held before the layout seam
    assert (full.slot_bytes, full.page_bytes) == (27371520, 15728640)
    assert full.pages * full.page_size == 65536


def test_laguna_sizes_follow_the_model():
    st = SequenceState(LAGUNA)
    # 3 sliding layers, K and V, 2 heads x window 16 x 16, float32
    assert st.slot_bytes == st.ring_bytes == 3 * 2 * 2 * 16 * 16 * 4
    # a ring array has one slot more than the manager counts: nobody's
    assert st.slot_shapes == {"ring_k": (3, 5, 2, 16, 16),
                              "ring_v": (3, 5, 2, 16, 16)}
    assert st.pool_shape == (2, 33, 2, 16, 16)
    assert st.page_bytes == 2 * 2 * 16 * 2 * 16 * 4
    arrays = st.allocate()
    assert {k: v.shape for k, v in arrays.items()} == {
        **st.slot_shapes, "k": st.pool_shape, "v": st.pool_shape,
        "token": (4,)}
    served = SequenceState(GenerateConfig(
        model=dataclasses.replace(LagunaConfig().cut(5),
                                  experts_held=(0, 128), vocab_size=50176),
        slots=64, kv_pages=576))
    # a ring of 512 keys and values of 8 heads of 128, three layers; a page
    # of 256 tokens of two full layers
    assert served.slot_bytes == 3 * 2 * 8 * 512 * 128 * 2 == 6291456
    assert served.page_bytes == 2 * 2 * 8 * 256 * 128 * 2 == 2097152


def test_phi4flash_sizes_follow_the_model():
    st = SequenceState(PHI4FLASH)
    # 3 Mamba layers: state [4, 128] float32 and 3 rows of 128; 2 window
    # layers, key pairs and values, 2 pairs x window 16 x 16, float32
    assert st.ring_bytes == 2 * 2 * 2 * 16 * 16 * 4
    assert st.slot_bytes == 3 * (4 * 128 * 4 + 3 * 128 * 4) + st.ring_bytes
    assert st.slot_shapes == {"ssm": (3, 4, 4, 128), "conv": (3, 4, 3, 128),
                              "ring_k": (2, 5, 2, 16, 16),
                              "ring_v": (2, 5, 2, 16, 16)}
    # one paged layer: 2 key pairs of 16, read by 4 query rows each
    assert st.pool_shape == (1, 33, 2, 16, 16)
    assert st.page_bytes == 2 * 16 * 2 * 16 * 4
    assert st.layout.query_group == 4
    served = SequenceState(GenerateConfig(
        model=Phi4FlashConfig(), slots=128, kv_pages=640,
        decode_buckets=(32, 64, 96, 128)))
    # nine states [16, 5120] float32 with 3 rows of 5120, eight rings of 512
    # key pairs and values of 10 x 128; a page of 256 tokens of one layer
    assert served.slot_bytes == (9 * (16 * 5120 * 4 + 3 * 5120 * 2)
                                 + 8 * 2 * 10 * 512 * 128 * 2) == 24197120
    assert served.page_bytes == 2 * 10 * 256 * 128 * 2 == 1310720
    assert served.pool_shape == (1, 641, 10, 256, 128)


@pytest.mark.parametrize("gen,slot_bytes,page_bytes", [
    (GEN, 21504, 16384), (LAGUNA, 12288, 8192), (PHI4FLASH, 18944, 4096)],
    ids=["olmo_hybrid", "laguna", "phi4flash"])
def test_slot_and_page_bytes_of_the_three_layouts(gen, slot_bytes,
                                                  page_bytes):
    """What admission is charged, by layout: held to the byte."""
    st = SequenceState(gen)
    assert (st.slot_bytes, st.page_bytes) == (slot_bytes, page_bytes)
    assert st.capacity_bytes == 4 * slot_bytes + 32 * page_bytes


def test_a_long_prompt_holds_no_more_ring_bytes_than_a_short_one():
    """A sliding layer holds no page for tokens its window has left behind:
    16 windows of prompt cost the same slot bytes as one, and the gauge
    says so."""
    st = SequenceState(LAGUNA)
    gauge = obs.REGISTRY.gauge("vmt_seq_ring_bytes_in_use")
    short = st.admit(16, 4)
    assert gauge.collect()[()] == st.ring_bytes
    long = st.admit(16 * 16, 4)
    assert gauge.collect()[()] == 2 * st.ring_bytes
    assert st.bytes_in_use == (2 * st.slot_bytes + (len(short.pages)
                               + len(long.pages)) * st.page_bytes)
    assert len(long.pages) == 17 and len(short.pages) == 2
    st.release(short)
    st.release(long)
    assert gauge.collect()[()] == 0


@BOTH
def test_admit_reserves_slot_and_pages_and_release_frees_them(gen):
    st = SequenceState(gen)
    seq = st.admit(prompt_len=40, max_new_tokens=8)   # 48 tokens: 3 pages
    assert (seq.slot, seq.pages) == (0, [0, 1, 2])
    assert st.bytes_in_use == st.slot_bytes + 3 * st.page_bytes
    assert list(st.page_slot[:4]) == [0, 0, 0, -1]
    assert list(st.page_pos[:3]) == [0, 1, 2]
    row = st.page_row(seq)
    assert list(row[:4]) == [0, 1, 2, 32]            # padding: nobody's page
    assert st.page_of(seq, 33) == 2 and st.pool_blocks(4) == 1
    st.release(seq)
    assert st.bytes_in_use == 0 and not st.live()
    assert (st.page_slot == -1).all() and st.pool_blocks(4) == 0
    with pytest.raises(ValueError):
        st.release(seq)


@BOTH
@pytest.mark.parametrize("reason,change,first,second", [
    ("no_slot", dict(slots=1), (8, 8), (8, 8)),
    ("no_pages", {}, (400, 8), (200, 8)),
    ("no_bytes", dict(state_bytes_budget=200000), (100, 8), (100, 8)),
])
def test_refuses_by_what_runs_out(gen, reason, change, first, second):
    if reason == "no_bytes" and gen is not GEN:
        # its state is smaller: a budget one admission fits and two do not
        probe = SequenceState(gen)
        change = dict(state_bytes_budget=int(1.5 * (
            probe.slot_bytes + 7 * probe.page_bytes)))
    st = SequenceState(dataclasses.replace(gen, **change))
    before = counter("vmt_seq_admit_refused_total", reason=reason)
    held = st.admit(*first)
    assert held is not None
    assert st.admit(*second) is None
    assert counter("vmt_seq_admit_refused_total",
                   reason=reason) == before + 1
    st.release(held)
    assert st.admit(*second) is not None


@BOTH
def test_200_random_rounds_leak_nothing_and_share_no_page(gen):
    st = SequenceState(gen)
    rng = np.random.default_rng(7)
    live = []
    for _ in range(200):
        if live and rng.random() < 0.45:
            st.release(live.pop(int(rng.integers(len(live)))))
        else:
            seq = st.admit(int(rng.integers(1, 200)), int(rng.integers(1, 9)))
            if seq is not None:
                live.append(seq)
        pages = [p for s in live for p in s.pages]
        assert len(pages) == len(set(pages))
        assert len({s.slot for s in live}) == len(live)
        assert st.bytes_in_use == sum(
            st.slot_bytes + len(s.pages) * st.page_bytes for s in live)
        assert st.bytes_in_use <= st.budget_bytes
        for s in live:
            assert (st.page_slot[s.pages] == s.slot).all()
            assert list(st.page_pos[s.pages]) == list(range(len(s.pages)))
        assert (st.page_slot >= 0).sum() == len(pages)
        assert st.stats()["kv_pages_in_use"] == len(pages)
    for seq in live:
        st.release(seq)
    assert st.bytes_in_use == 0 and not st.live()
    assert st.stats() == {"seq_slots_in_use": 0.0, "kv_pages_in_use": 0.0,
                          "seqstate_bytes_in_use": 0.0}
    assert (st.page_slot == -1).all()


@BOTH
def test_unwritten_bytes_count_what_nothing_was_written_to(gen):
    st = SequenceState(gen)
    assert st.unwritten_bytes() == st.capacity_bytes
    seq = st.admit(40, 8)
    st.note_written(seq, 20)                        # 2 of its 3 pages
    assert st.unwritten_bytes() == (st.capacity_bytes - st.slot_bytes
                                    - 2 * st.page_bytes)
    st.release(seq)                                  # written stays written
    assert st.unwritten_bytes() == (st.capacity_bytes - st.slot_bytes
                                    - 2 * st.page_bytes)
