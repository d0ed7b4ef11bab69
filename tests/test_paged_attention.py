"""The paged decode attention kernel against its oracle (ISSUE 29): the
Pallas kernel of ``ops/paged_attention.py`` in interpret mode and
``models/decoder.py:_decode_attention`` (the ``jax.numpy`` form the CPU
serves with) on the same pools, for every decode bucket and four states of
the pool.

Every pool has holes (pages nobody owns between owned ones), a slot that
owns nothing (no key: the kernel gives exactly 0, never NaN), sequences
that end in the middle of a page, pages handed out out of order, a page
whose owner lies beyond the batch, and is read at the second layer index
while the first holds other numbers.

The prefill kernel (ISSUE 33) follows, against
``models/decoder.py:_prefill_attention`` on pools whose sequence's pages
are scattered and out of order.

Tolerance: both are float32 on the CPU and differ by summation order (the
oracle folds a block of pages at a time, the kernel a page), read at 1e-6
on outputs of unit size: 5e-5 allowed, as ``tests/test_gated_delta.py``.
The kernel's compile for the chip at the served size is in that file too,
beside the scan's: one file describes the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vilbert_multitask_tpu.config import OlmoHybridConfig
from vilbert_multitask_tpu.models import olmo_hybrid as model_lib
from vilbert_multitask_tpu.ops import paged_attention

ATOL = 5e-5
LAYERS, PAGES, HEADS, PAGE, WIDTH, BLOCK = 2, 24, 3, 16, 32, 8
LAYER = 1

# pool state -> (pages the live sequences may take, ``pool_blocks``)
POOLS = {
    "empty": (0, 0),
    "one_block": (BLOCK, 1),
    "partly_used_last_block": (2 * BLOCK + 3, 3),
    "every_block": (PAGES, 3),
}


def operands(B, seed):
    """Queries and both pools, every layer filled."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (LAYERS, PAGES + 1, HEADS, PAGE, WIDTH)
    return (jax.random.normal(ks[0], (B, HEADS, WIDTH)),
            jax.random.normal(ks[1], shape), jax.random.normal(ks[2], shape))


def tables(B, reach, rng):
    """Sequences over the first ``reach`` pages: every third page left to
    nobody, the rest dealt in shuffled order to the even slots of the batch
    (the odd ones own nothing), one page to a slot beyond the batch; each
    sequence ends somewhere inside its last page."""
    page_slot = np.full((PAGES,), -1, np.int32)
    page_pos = np.zeros((PAGES,), np.int32)
    positions = np.zeros((B,), np.int32)
    free = [i for i in range(reach) if i % 3 != 1]
    rng.shuffle(free)
    if free:
        page_slot[free.pop()] = B + 1
    owners = list(range(0, B, 2))
    held = {b: [] for b in owners}
    for n, page in enumerate(free):
        held[owners[n % len(owners)]].append(page)
    for b, pages in held.items():
        for nth, page in enumerate(pages):
            page_slot[page], page_pos[page] = b, nth
        if pages:
            positions[b] = (len(pages) - 1) * PAGE + rng.integers(0, PAGE)
    return page_slot, page_pos, positions, [b for b in owners if held[b]]


@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("B", [8, 16, 24, 32])
def test_kernel_equals_the_jnp_form(B, pool):
    reach, pool_blocks = POOLS[pool]
    rng = np.random.default_rng(B * 7 + reach)
    page_slot, page_pos, positions, with_keys = tables(B, reach, rng)
    args = (*operands(B, B + reach), LAYER, jnp.asarray(positions),
            jnp.asarray(page_slot), jnp.asarray(page_pos),
            jnp.int32(pool_blocks), BLOCK)
    want = model_lib._decode_attention(OlmoHybridConfig().tiny(), *args)
    got = paged_attention.paged_decode_attention(*args, interpret=True)
    assert got.shape == want.shape == (B, HEADS, WIDTH)
    assert got.dtype == jnp.float32
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    assert (reach == 0) == (not with_keys)
    if with_keys:
        assert np.abs(got[with_keys] - want[with_keys]).max() < ATOL
        assert np.abs(got[with_keys]).max() > 0.1
    without = sorted(set(range(B)) - set(with_keys))
    assert (got[without] == 0).all()


def test_a_key_beyond_its_slots_position_is_not_read():
    """Two pages of one sequence, the position inside the first: the second
    page's keys, and the first's behind the position, change nothing."""
    B = 8
    page_slot = np.full((PAGES,), -1, np.int32)
    page_pos = np.zeros((PAGES,), np.int32)
    page_slot[[5, 2]], page_pos[[5, 2]] = 3, [0, 1]
    positions = np.zeros((B,), np.int32)
    positions[3] = 6
    q, k_pool, v_pool = operands(B, 0)

    def run(k_pool, v_pool):
        return np.asarray(paged_attention.paged_decode_attention(
            q, k_pool, v_pool, LAYER, jnp.asarray(positions),
            jnp.asarray(page_slot), jnp.asarray(page_pos), jnp.int32(1),
            BLOCK, interpret=True))

    seen = run(k_pool, v_pool)
    unseen = run(k_pool.at[LAYER, 2].set(9.0).at[LAYER, 5, :, 7:].set(9.0),
                 v_pool.at[LAYER, 2].set(9.0).at[LAYER, 5, :, 7:].set(9.0))
    assert np.abs(seen[3]).max() > 0.1
    assert (seen == unseen).all()
    # The plain softmax over the seven keys the mask admits.
    keys, values = (np.asarray(t[LAYER, 5, :, :7]) for t in (k_pool, v_pool))
    scores = np.einsum("hd,hkd->hk", np.asarray(q[3]), keys) / np.sqrt(WIDTH)
    weights = np.exp(scores - scores.max(-1, keepdims=True))
    weights /= weights.sum(-1, keepdims=True)
    assert np.abs(np.einsum("hk,hkd->hd", weights, values)
                  - seen[3]).max() < ATOL


@pytest.mark.parametrize("group", [1, 6, 9])
def test_grouped_queries_read_their_key_value_head(group):
    """``group`` query heads a key/value head (ISSUE 32: 6 in laguna's full
    layers, 9 would be its sliding ones'): the kernel against the
    ``jax.numpy`` form, and one slot against the plain softmax in which
    query head ``h`` reads key/value head ``h // group``."""
    B, reach, pool_blocks = 8, 2 * BLOCK + 3, 3
    rng = np.random.default_rng(group)
    page_slot, page_pos, positions, with_keys = tables(B, reach, rng)
    _, k_pool, v_pool = operands(B, group)
    q = jax.random.normal(jax.random.PRNGKey(group),
                          (B, HEADS * group, WIDTH))
    args = (q, k_pool, v_pool, LAYER, jnp.asarray(positions),
            jnp.asarray(page_slot), jnp.asarray(page_pos),
            jnp.int32(pool_blocks), BLOCK)
    want = np.asarray(model_lib._decode_attention(
        OlmoHybridConfig().tiny(), *args))
    got = np.asarray(paged_attention.paged_decode_attention(
        *args, interpret=True))
    assert got.shape == (B, HEADS * group, WIDTH)
    assert np.abs(got[with_keys] - want[with_keys]).max() < ATOL
    b = with_keys[0]
    pages = sorted(np.nonzero(page_slot == b)[0], key=lambda p: page_pos[p])
    keys, values = (np.concatenate([np.asarray(t[LAYER, p]) for p in pages],
                                   axis=1)[:, :positions[b] + 1]
                    for t in (k_pool, v_pool))           # [HEADS, n, WIDTH]
    for h in range(HEADS * group):
        scores = keys[h // group] @ np.asarray(q[b, h]) / np.sqrt(WIDTH)
        weights = np.exp(scores - scores.max())
        plain = (weights / weights.sum()) @ values[h // group]
        assert np.abs(plain - got[b, h]).max() < ATOL


def test_heads_that_do_not_divide_are_refused():
    q, k_pool, v_pool = operands(8, 0)
    with pytest.raises(ValueError, match="do not divide"):
        paged_attention.paged_decode_attention(
            jnp.concatenate([q, q[:, :1]], 1), k_pool, v_pool, LAYER,
            jnp.zeros((8,), jnp.int32), jnp.full((PAGES,), -1, jnp.int32),
            jnp.zeros((PAGES,), jnp.int32), jnp.int32(0), BLOCK,
            interpret=True)


# ----------------------------------------------------------------- prefill
def prefill_operands(T, group, seed, pages=PAGES):
    """A chunk's queries, both pools (every layer filled) and the
    sequence's table: every page of the pool, shuffled."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (LAYERS, pages + 1, HEADS, PAGE, WIDTH)
    row = np.random.default_rng(seed).permutation(pages).astype(np.int32)
    return (jax.random.normal(ks[0], (T, HEADS * group, WIDTH)),
            jax.random.normal(ks[1], shape), jax.random.normal(ks[2], shape),
            jnp.asarray(row))


def prefill_both(q, k_pool, v_pool, row, start):
    want = model_lib._prefill_attention(
        OlmoHybridConfig().tiny(), q, k_pool, v_pool, LAYER, row,
        jnp.int32(start), 2)
    got = paged_attention.paged_prefill_attention(
        q, k_pool, v_pool, LAYER, row, jnp.int32(start), interpret=True)
    assert got.shape == want.shape == q.shape and got.dtype == jnp.float32
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("tile_rows", [8, 48, 768])
@pytest.mark.parametrize("start_pages", [0, 13])
@pytest.mark.parametrize("bucket_pages", [1, 8])
@pytest.mark.parametrize("group", [1, 6])
def test_prefill_kernel_equals_the_jnp_form(monkeypatch, group, bucket_pages,
                                            start_pages, tile_rows):
    """Group 1 and 6, a bucket of one page and of eight, at the start of a
    prompt and thirteen pages into it; tiles shorter than a page, a page
    long and the whole chunk (``_TILE_ROWS`` is what the served shapes
    reach with thousands of rows; here it is turned down so that the tiny
    chunk has several tiles too)."""
    monkeypatch.setattr(paged_attention, "_TILE_ROWS", tile_rows)
    T = bucket_pages * PAGE
    args = prefill_operands(T, group, group + bucket_pages + start_pages)
    got, want = prefill_both(*args, start_pages * PAGE)
    assert np.abs(got - want).max() < ATOL
    assert np.abs(got).max() > 0.1


def test_prefill_kernel_with_a_chunk_shorter_than_its_bucket():
    """``length < T``: the padded rows' pages are nobody's page (the
    table's unreserved entries name it, and it holds whatever the last
    padding wrote); the real rows agree, the padded ones are finite."""
    T, start, length = 4 * PAGE, 3 * PAGE, PAGE + 5
    q, k_pool, v_pool, row = prefill_operands(T, 6, 3)
    reserved = -(-(start + length) // PAGE)
    row = jnp.where(jnp.arange(PAGES) < reserved, row, PAGES)
    got, want = prefill_both(q, k_pool, v_pool, row, start)
    assert np.abs(got[:length] - want[:length]).max() < ATOL
    assert np.isfinite(got).all()


def test_a_key_after_the_querys_position_is_not_read(monkeypatch):
    """Poison every key and value behind each query's position (the rest
    of the chunk's pages and all later ones): nothing moves. Row 0 of a
    chunk that starts a prompt is its first value alone."""
    monkeypatch.setattr(paged_attention, "_TILE_ROWS", 8)
    T, start = 2 * PAGE, 2 * PAGE
    q, k_pool, v_pool, row = prefill_operands(T, 1, 5)
    pages = np.asarray(row)

    def run(k_pool, v_pool, q=q, start=start):
        return np.asarray(paged_attention.paged_prefill_attention(
            q, k_pool, v_pool, LAYER, row, jnp.int32(start), interpret=True))

    seen = run(k_pool, v_pool)
    # The last query (position start + T - 1) sees all of pages 0..3; a
    # query at start + 3 sees four keys of page 2: compare it alone under
    # poison behind it.
    behind = (k_pool.at[LAYER, pages[2], :, 4:].set(1e4)
              .at[LAYER, pages[3:]].set(1e4),
              v_pool.at[LAYER, pages[2], :, 4:].set(1e4)
              .at[LAYER, pages[3:]].set(1e4))
    assert (run(*behind)[:4] == seen[:4]).all()
    after_chunk = (k_pool.at[LAYER, pages[4:]].set(1e4),
                   v_pool.at[LAYER, pages[4:]].set(1e4))
    assert (run(*after_chunk) == seen).all()
    first = run(k_pool, v_pool, start=0)[0]
    assert np.abs(first - np.asarray(v_pool[LAYER, pages[0], :, 0])
                  ).max() < ATOL


def test_prefill_heads_that_do_not_divide_are_refused():
    q, k_pool, v_pool, row = prefill_operands(PAGE, 1, 0)
    with pytest.raises(ValueError, match="do not divide"):
        paged_attention.paged_prefill_attention(
            jnp.concatenate([q, q[:, :1]], 1), k_pool, v_pool, LAYER, row,
            jnp.int32(0), interpret=True)


@pytest.mark.parametrize("T,group,tq", [(2048, 6, 128), (256, 6, 128),
                                        (2048, 1, 512), (256, 1, 256),
                                        (96, 6, 96), (96, 9, 48)])
def test_the_tile_follows_from_the_shapes(T, group, tq):
    assert paged_attention.prefill_tile(T, group) == tq


@pytest.mark.parametrize("start,T,group,max_pages,steps", [
    (0, 2048, 6, 576, sum(range(1, 9)) * 2),      # 16 tiles of half a page
    (30720, 2048, 6, 576, 16 * 120 + sum(range(1, 9)) * 2),
    (0, 2048, 1, 256, 2 + 4 + 6 + 8),             # 4 tiles of two pages
    (0, 256, 1, 256, 1),
    (65024, 1024, 1, 256, 256 + 256),             # never past the table
])
def test_pages_walked_are_reckoned_from_start_and_tile(start, T, group,
                                                       max_pages, steps):
    assert paged_attention.prefill_pages_walked(
        start, T, 256, group, max_pages) == steps


# --------------------------------------------------------------------- ring
# The ring kernels (ISSUE 34): a decode step's attention over the slots'
# rings read in place, against ``models/decoder.py:_sliding_decode``, and the
# store of one slot's ring, against a ``dynamic_update_slice``.
RING_LAYERS, RING_SLOTS, RING_ROWS = 3, 6, 16


def ring_operands(B, group, heads=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (RING_LAYERS, RING_SLOTS + 1, heads, RING_ROWS, WIDTH)
    return (jax.random.normal(ks[0], (B, heads * group, WIDTH)),
            jax.random.normal(ks[1], shape), jax.random.normal(ks[2], shape))


@pytest.mark.parametrize("window", [RING_ROWS, 9])
@pytest.mark.parametrize("group", [1, 4, 9])
def test_ring_kernel_equals_the_jnp_form(group, window):
    """Positions before the ring is full (rows past them hold other
    numbers and are not read), exactly full, and many turns later; a window
    shorter than the ring."""
    from vilbert_multitask_tpu.models.decoder import _sliding_decode

    B = 5
    q, ring_k, ring_v = ring_operands(B, group)
    positions = jnp.asarray([0, 3, RING_ROWS - 1, RING_ROWS, 1000], jnp.int32)
    want = _sliding_decode(window, q, ring_k[LAYER, :B], ring_v[LAYER, :B],
                           positions)
    got = paged_attention.ring_decode_attention(
        q, ring_k, ring_v, LAYER, positions, window, interpret=True)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert float(jnp.abs(got - want).max()) < ATOL


def test_ring_heads_that_do_not_divide_are_refused():
    q, ring_k, ring_v = ring_operands(4, 1)
    with pytest.raises(ValueError, match="do not divide"):
        paged_attention.ring_decode_attention(
            q[:, :1].repeat(3, axis=1), ring_k, ring_v, 0,
            jnp.zeros((4,), jnp.int32), RING_ROWS, interpret=True)


@pytest.mark.parametrize("slot", [0, 4])
def test_ring_store_replaces_one_slots_ring_and_nothing_else(slot):
    _, ring, _ = ring_operands(4, 1)
    new = jax.random.normal(jax.random.PRNGKey(9), ring.shape[2:])
    got = jax.jit(lambda r, at, n: paged_attention.ring_store(
        r, LAYER, at, n, interpret=True))(ring, jnp.int32(slot), new)
    assert (np.asarray(got) == np.asarray(ring.at[LAYER, slot].set(new))
            ).all()


def test_an_inactive_rows_key_goes_to_nobodys_slot():
    """``_write_ring`` leaves an inactive slot's ring as it was: its row
    lands in the array's last slot, which no sequence owns."""
    from vilbert_multitask_tpu.models.decoder import _write_ring

    _, ring, _ = ring_operands(4, 1)
    rows = jax.random.normal(jax.random.PRNGKey(3), (3, 2, WIDTH))
    index = jnp.asarray([2, 5, 7], jnp.int32)
    active = jnp.asarray([True, False, True])
    got = np.asarray(_write_ring(ring, LAYER, rows, index, active))
    want = np.asarray(ring).copy()
    want[LAYER, 0, :, 2] = rows[0]
    want[LAYER, RING_SLOTS, :, 5] = rows[1]
    want[LAYER, 2, :, 7] = rows[2]
    assert (got == want).all()
