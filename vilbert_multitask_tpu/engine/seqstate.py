"""The sequence-state manager of the generate engine: one object, one byte
budget, two kinds of state side by side.

What the two kinds *are* is the model's to say: its module states a
:class:`~vilbert_multitask_tpu.models.decoder.StateLayout`
(``state_layout``), and this manager allocates and accounts it without
knowing a layer kind. *Slot state*: what a running sequence holds at a
fixed size whatever its length, each array ``lead + (slots,) + shape``
(``olmo_hybrid``: every linear-attention layer's recurrent state ``[heads,
d_k, d_v]`` float32 and the last ``taps - 1`` rows that entered its
convolution; ``laguna``: every sliding layer's last ``sliding_window`` keys
and values, a ring over positions, so that a window layer holds no page
for tokens its window has left behind). *Paged state*: what grows with the
sequence: for every full-attention layer keys and values in pages of
``page_size`` tokens, ``[layer, page, head, token, d]`` (a head's keys of a
page are one tile: what ``ops/paged_attention.py`` reads), and per
sequence the list of its pages in order.

The device side is a dict of arrays (:meth:`allocate`), handed to the
compiled programs *donated* and taken back updated: a prefill chunk writes
one slot's slice and the pages of its tokens, a decode step scatters the
rows of its sequences; nothing is ever copied whole. The host side, here,
is the accounting: :meth:`admit` reserves a slot and ``ceil((prompt + new)
/ page_size)`` pages, or says no (no slot, no pages, or the byte budget);
:meth:`release` frees both. Pages are handed out lowest number first, so
the part of the pool decode has to read (:meth:`pool_blocks`) stays as
short as the load allows.

Invariants (``tests/test_seqstate.py`` holds them): a page belongs to at
most one live sequence; ``bytes_in_use`` is the sum over live sequences of
their slot and page bytes; after every sequence is released nothing is in
use.

One thread (the scheduler's dispatch loop) admits and releases; the
sampler reads the gauges. A lock makes that safe, and nothing blocks under
it.
"""

from __future__ import annotations

import dataclasses
import heapq
import threading
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.config import GenerateConfig
from vilbert_multitask_tpu.models.decoder import StateLayout

_ADMITTED = obs.REGISTRY.counter(
    "vmt_seq_admitted_total", "Sequences the state manager admitted.")
_REFUSED = obs.REGISTRY.counter(
    "vmt_seq_admit_refused_total",
    "Admissions the state manager refused, by what ran out.",
    labelnames=("reason",))
_RELEASED = obs.REGISTRY.counter(
    "vmt_seq_released_total", "Sequences released (slot and pages freed).")
_SLOTS_IN_USE = obs.REGISTRY.gauge(
    "vmt_seq_slots_in_use", "Sequence slots held by live sequences.")
_PAGES_IN_USE = obs.REGISTRY.gauge(
    "vmt_kv_pages_in_use", "Key/value pages held by live sequences.")
_BYTES_IN_USE = obs.REGISTRY.gauge(
    "vmt_seqstate_bytes_in_use",
    "Device bytes of sequence state held by live sequences.")
_RING_BYTES_IN_USE = obs.REGISTRY.gauge(
    "vmt_seq_ring_bytes_in_use",
    "Device bytes of window rings (a sliding layer's last keys and values) "
    "held by live sequences; part of vmt_seqstate_bytes_in_use.")
_PAGES_TOTAL = obs.REGISTRY.gauge(
    "vmt_kv_pages_total", "Key/value pages the pool has.")


@dataclasses.dataclass
class Sequence:
    """One admitted sequence: where its state lives, and how far it is."""

    slot: int
    pages: List[int]
    prompt_len: int
    max_new_tokens: int
    prefilled: int = 0      # prompt tokens dispatched
    generated: int = 0      # tokens dispatched (the first by the prefill)

    @property
    def prefilling(self) -> bool:
        return self.prefilled < self.prompt_len

    @property
    def done(self) -> bool:
        return self.generated >= self.max_new_tokens


class SequenceState:
    def __init__(self, gen: GenerateConfig, layout: StateLayout):
        self.gen = gen
        self.layout = layout
        self.slots = int(gen.slots)
        self.pages = int(gen.kv_pages)
        self.page_size = int(gen.page_size)
        if self.pages % min(gen.decode_attention_pages, self.pages):
            raise ValueError("kv_pages must be a multiple of "
                             "decode_attention_pages")
        # A ring has one slot more than the manager counts: the last
        # belongs to nobody (``models/decoder.py:_write_ring``).
        self.slot_shapes = {
            name: a.lead + (self.slots + int(a.ring),) + a.shape
            for name, a in layout.slot_arrays.items()}
        # One page more than the pool counts: the last belongs to nobody,
        # and is where a padding row's keys and values are written.
        self.pool_shape = (layout.paged_layers, self.pages + 1,
                           layout.kv_heads, self.page_size, layout.head_dim)
        self.slot_bytes = sum(a.slot_bytes
                              for a in layout.slot_arrays.values())
        self.ring_bytes = sum(a.slot_bytes
                              for a in layout.slot_arrays.values() if a.ring)
        self.page_bytes = 2 * int(np.prod(
            (layout.paged_layers, layout.kv_heads, self.page_size,
             layout.head_dim))) * jnp.dtype(layout.dtype).itemsize
        self.capacity_bytes = (self.slots * self.slot_bytes
                               + self.pages * self.page_bytes)
        self.budget_bytes = (self.capacity_bytes
                             if gen.state_bytes_budget is None
                             else int(gen.state_bytes_budget))
        self.max_pages_per_seq = min(
            self.pages,
            -(-gen.model.max_position_embeddings // self.page_size))
        self.arrays: Optional[dict] = None
        self._lock = threading.Lock()
        self._free_slots = list(range(self.slots))
        heapq.heapify(self._free_slots)
        self._free_pages = list(range(self.pages))
        heapq.heapify(self._free_pages)
        self._live: Dict[int, Sequence] = {}
        # Whose each page is (-1: nobody's) and which of its sequence's
        # pages: what decode's ownership mask is made of.
        self.page_slot = np.full((self.pages,), -1, np.int32)
        self.page_pos = np.zeros((self.pages,), np.int32)
        # What was ever written to: the rest is reserved, not held
        # (the benchmark's ``written_share``).
        self._slots_written = set()
        self._pages_written = set()
        self.bytes_in_use = 0
        _PAGES_TOTAL.set(self.pages)
        self._publish()

    # ---------------------------------------------------------- device side
    def allocate(self) -> dict:
        """The device arrays, zeroed; kept as ``self.arrays``, which the
        engine replaces with what each donated call returns."""
        dtype = jnp.dtype(self.layout.dtype)
        self.arrays = {
            name: jnp.zeros(shape, self.layout.slot_arrays[name].dtype)
            for name, shape in self.slot_shapes.items()}
        self.arrays.update(
            k=jnp.zeros(self.pool_shape, dtype),
            v=jnp.zeros(self.pool_shape, dtype),
            token=jnp.zeros((self.slots,), jnp.int32))
        return self.arrays

    def drop_arrays(self) -> None:
        """Free the device side (the app is stopping)."""
        arrays, self.arrays = self.arrays, None
        for a in (arrays or {}).values():
            a.delete()

    # ------------------------------------------------------------ host side
    def pages_for(self, prompt_len: int, max_new_tokens: int) -> int:
        return -(-(prompt_len + max_new_tokens) // self.page_size)

    def admit(self, prompt_len: int, max_new_tokens: int
              ) -> Optional[Sequence]:
        """A slot and the pages of ``prompt_len + max_new_tokens`` tokens,
        or None: the caller keeps the job and asks again later."""
        with obs.span("seqstate.admit", prompt_len=prompt_len) as sp, \
                self._lock:
            need = self.pages_for(prompt_len, max_new_tokens)
            reason = None
            if not self._free_slots:
                reason = "no_slot"
            elif need > len(self._free_pages):
                reason = "no_pages"
            elif (self.bytes_in_use + self.slot_bytes
                  + need * self.page_bytes > self.budget_bytes):
                reason = "no_bytes"
            if reason is not None:
                _REFUSED.inc(reason=reason)
                sp.set(refused=reason)
                return None
            slot = heapq.heappop(self._free_slots)
            pages = [heapq.heappop(self._free_pages) for _ in range(need)]
            for pos, page in enumerate(pages):
                self.page_slot[page] = slot
                self.page_pos[page] = pos
            seq = Sequence(slot, pages, prompt_len, max_new_tokens)
            self._live[slot] = seq
            self.bytes_in_use += self.slot_bytes + need * self.page_bytes
            _ADMITTED.inc()
            self._publish()
            return seq

    def release(self, seq: Sequence) -> None:
        with self._lock:
            if self._live.pop(seq.slot, None) is not seq:
                raise ValueError(f"slot {seq.slot} is not this sequence's")
            heapq.heappush(self._free_slots, seq.slot)
            for page in seq.pages:
                self.page_slot[page] = -1
                heapq.heappush(self._free_pages, page)
            self.bytes_in_use -= (self.slot_bytes
                                  + len(seq.pages) * self.page_bytes)
            _RELEASED.inc()
            self._publish()

    def page_row(self, seq: Sequence) -> np.ndarray:
        """The sequence's pages in order, padded with nobody's page (the
        pool's last): what a padding entry writes lands there."""
        row = np.full((self.max_pages_per_seq,), self.pages, np.int32)
        row[:len(seq.pages)] = seq.pages
        return row

    def page_of(self, seq: Sequence, position: int) -> int:
        return seq.pages[position // self.page_size]

    def pool_blocks(self, block: int) -> int:
        """Blocks of ``block`` pages that reach past the last page in use."""
        used = np.nonzero(self.page_slot >= 0)[0]
        return 0 if used.size == 0 else int(used[-1]) // block + 1

    def note_written(self, seq: Sequence, tokens: int) -> None:
        """The sequence's state now holds ``tokens`` tokens."""
        self._slots_written.add(seq.slot)
        self._pages_written.update(
            seq.pages[:-(-tokens // self.page_size)])

    def unwritten_bytes(self) -> int:
        return ((self.slots - len(self._slots_written)) * self.slot_bytes
                + (self.pages - len(self._pages_written)) * self.page_bytes)

    def live(self) -> List[Sequence]:
        with self._lock:
            return list(self._live.values())

    def stats(self) -> Dict[str, float]:
        with self._lock:
            return {"seq_slots_in_use": float(len(self._live)),
                    "kv_pages_in_use":
                        float(self.pages - len(self._free_pages)),
                    "seqstate_bytes_in_use": float(self.bytes_in_use)}

    def _publish(self) -> None:
        _SLOTS_IN_USE.set(len(self._live))
        _PAGES_IN_USE.set(self.pages - len(self._free_pages))
        _BYTES_IN_USE.set(self.bytes_in_use)
        _RING_BYTES_IN_USE.set(len(self._live) * self.ring_bytes)
