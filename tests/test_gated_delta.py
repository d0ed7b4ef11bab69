"""The gated delta rule's three forms agree (ISSUE 28, test c): the chunked
scan in ``jax.numpy`` and the Pallas kernel in interpret mode against the
recurrence one token at a time, with beta in (1, 2) present (negative
eigenvalues) and alpha near 0 and near 1.

Tolerance: all three are float32 at ``highest`` precision; they differ by
summation order and by the chunk's system, which the chunked forms solve by
an inverse built from batched matmuls (``gd.unit_lower_inverse``), read at
3e-6 on outputs of size 2: 5e-5 allowed. That inverse is held on its own to
a float64 solve of the same system, at 1e-5 relative, on inputs that make
the system hard (ISSUE 37: write strengths near 2, keys pulled toward one
direction) and at the served size. The TPU kernel's real-size compile is
the one thing here that is not CPU arithmetic (section 2 of the
on-chip-measurement guide: the chip's compiler runs without the chip). The
other kernels of the generate path (``ops/paged_attention.py``'s two,
``ops/moe.py``'s) are compiled for the chip here too (their arithmetic is
``tests/test_paged_attention.py``'s and ``tests/test_laguna.py``'s), and
so is the ViLBERT rows program's read of its device-resident slab (its
values are ``tests/test_engine.py``'s): one file describes the chip,
because one process at a time may load its compiler."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vilbert_multitask_tpu.ops import gated_delta as gd
from vilbert_multitask_tpu.ops import paged_attention, selective_scan

ATOL = 5e-5


def inputs(alpha, T=192, H=3, dk=8, dv=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (T, H, dk))
    k = jax.random.normal(ks[1], (T, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (T, H, dv))
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (T, H)))
    state = jax.random.normal(ks[5], (H, dk, dv))
    return q, k, v, jnp.log(alpha(ks[4], (T, H))), beta, state


ALPHAS = {
    "near_0": lambda key, shape: jnp.full(shape, 1e-4),
    "near_1": lambda key, shape: jnp.full(shape, 0.9999),
    "between": lambda key, shape: jax.random.uniform(
        key, shape, minval=0.5, maxval=1.0),
    "mixed": lambda key, shape: jnp.where(
        jax.random.uniform(key, shape) < 0.3, 1e-3, 0.999),
}


@pytest.mark.parametrize("form", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("alpha", sorted(ALPHAS))
def test_chunked_scan_equals_the_recurrence(alpha, form):
    q, k, v, g, beta, state = inputs(ALPHAS[alpha])
    assert float(beta.max()) > 1.5 and float(beta.min()) < 0.5
    want_o, want_s = gd.gated_delta_recurrent(q, k, v, g, beta, state)
    got_o, got_s = gd.gated_delta_chunked(
        q, k, v, g, beta, state, use_pallas=form != "jnp",
        interpret=form != "jnp")
    assert float(jnp.abs(want_o - got_o).max()) < ATOL
    assert float(jnp.abs(want_s - got_s).max()) < ATOL


def hard_inputs(alpha, T, H, dk, dv, seed=0):
    """Write strengths in (1.5, 2) and unit keys pulled toward a shared
    direction: the system's off-diagonal entries near 2 in size, where a
    Neumann series over the whole chunk diverges."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shared = jax.random.normal(ks[0], (dk,))
    k = 0.3 * jax.random.normal(ks[1], (T, H, dk)) \
        + shared / jnp.linalg.norm(shared)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q = jax.random.normal(ks[2], (T, H, dk)) / np.sqrt(dk)
    v = jax.random.normal(ks[3], (T, H, dv))
    beta = jax.random.uniform(ks[4], (T, H), minval=1.5, maxval=2.0)
    return q, k, v, jnp.log(alpha(ks[5], (T, H))), beta


def solve_float64(q, k, v, g, beta, chunk=gd.CHUNK):
    """``[u | w]`` of every chunk by ``numpy.linalg.solve`` in float64, the
    system and right-hand side built as the module docstring writes them."""
    T, H, _ = q.shape

    def heads_first(x):
        x = np.moveaxis(np.asarray(x, np.float64), 1, 0)
        return x.reshape(H, T // chunk, chunk, *x.shape[2:])

    q, k, v, g, beta = map(heads_first, (q, k, v, g, beta))
    logg = np.cumsum(g, axis=-1)
    t, i = np.tril_indices(chunk, -1)
    a = np.zeros(logg.shape + (chunk,))
    a[..., t, i] = np.exp(logg[..., t] - logg[..., i]) \
        * np.einsum("...tk,...tk->...t", k[..., t, :], k[..., i, :])
    system = np.eye(chunk) + beta[..., None] * a
    rhs = beta[..., None] * np.concatenate(
        [v, np.exp(logg)[..., None] * k], axis=-1)
    return np.linalg.solve(system, rhs)


@pytest.mark.parametrize("size", [(192, 3, 8, 16), (2048, 30, 96, 192)],
                         ids=["small", "served"])
@pytest.mark.parametrize("alpha", sorted(ALPHAS))
def test_preparation_solves_the_chunk_system(alpha, size):
    """``u`` and ``w`` of :func:`gd.chunk_prepare` against a float64 solve:
    the inverse of blocks merged by doubling is exact to float32's
    rounding, however near 2 the write strengths."""
    T, H, dk, dv = size
    args = hard_inputs(ALPHAS[alpha], T, H, dk, dv)
    prep = jax.jit(gd.chunk_prepare)(*args)
    want = solve_float64(*args)
    for got, ref in ((prep["u"], want[..., :dv]), (prep["w"], want[..., dv:])):
        err = np.abs(np.asarray(got, np.float64) - ref).max()
        assert err < 1e-5 * np.abs(ref).max()


def test_padding_tokens_leave_the_state_alone():
    """g = 0, beta = 0 (what the model gives a padded token): nothing is
    written, nothing decays."""
    q, k, v, g, beta, state = inputs(ALPHAS["between"], T=128)
    real = (jnp.arange(128) < 70)[:, None]
    g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    _, padded = gd.gated_delta_chunked(q, k, v, g, beta, state)
    _, short = gd.gated_delta_recurrent(q[:70], k[:70], v[:70], g[:70],
                                        beta[:70], state)
    assert float(jnp.abs(padded - short).max()) < ATOL


def test_one_step_is_the_equations():
    """S_t = a S + b (v - a S k) k^T, o = S_t q, on the transposed state."""
    rng = np.random.default_rng(0)
    S = rng.standard_normal((16, 8))            # [dv, dk]
    q, k, v = (rng.standard_normal(n) for n in (8, 8, 16))
    a, b = 0.9, 1.7
    want_S = a * S + b * np.outer(v - a * S @ k, k)
    o, new = gd.recurrent_step(jnp.asarray(S.T, jnp.float32), q, k, v,
                               jnp.log(a), jnp.asarray(b))
    assert np.abs(np.asarray(new).T - want_S).max() < 1e-5
    assert np.abs(np.asarray(o) - want_S @ q).max() < 1e-5


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever says "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_kernel_compiles_for_the_chip_at_the_served_size(one_chip):
    """30 heads, a 2048-token chunk, d_k 96, d_v 192: what the interpreter
    cannot show (tiling, VMEM) the chip's compiler refuses here."""
    H, N, C, dk, dv = 30, 32, gd.CHUNK, 96, 192

    def sd(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(gd.gated_delta_scan).lower(
        sd(H, N, C, dv), sd(H, N, C, dk), sd(H, N, C, dk), sd(H, N, C, C),
        sd(H, N, dk, C), sd(H, N, 1, 1), sd(H, dk, dv)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The profile's event carries the kernel's name and both results'
    # shapes: what benchmark/reduce/kinds/trace_gated_delta_roofline.py
    # reads.
    assert "%gated_delta_scan" in text
    assert "(f32[30,32,64,192]" in text and "f32[30,96,192]" in text


def test_preparation_compiles_without_a_triangular_solve(one_chip):
    """The preparation of a 2048-token chunk of 30 heads (d_k 96, d_v 192):
    no ``triangular-solve`` and no ``InvertDiagBlocksLowerTriangular``
    custom call of ``f32[30,32,1,64,64]``, which walked each 64 x 64 block
    row by row and took a tenth of ``olmo.longdocs``'s chip (ISSUE 37)."""
    T, H, dk, dv = 2048, 30, 96, 192

    def sd(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    text = jax.jit(gd.chunk_prepare).lower(
        sd(T, H, dk), sd(T, H, dk), sd(T, H, dv), sd(T, H),
        sd(T, H)).compile().as_text()
    assert "triangular-solve" not in text
    results = [line.split(" custom-call(")[0] for line in text.splitlines()
               if " custom-call(" in line]
    assert not any("f32[30,32,1,64,64]" in r for r in results)


@pytest.mark.parametrize("B", [8, 16, 24, 32])
def test_paged_attention_compiles_for_the_chip_at_the_served_size(one_chip,
                                                                  B):
    """Every decode bucket over the served pool: 4 layers of 256 + 1 pages
    of 30 heads x 256 tokens x 128, bfloat16, read in place (no temporary:
    a copy of a pool would be 2 GB)."""
    def sd(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sd(jnp.bfloat16, 4, 257, 30, 256, 128)
    compiled = jax.jit(
        lambda q, k, v, positions, page_slot, page_pos, pool_blocks:
        paged_attention.paged_decode_attention(
            q, k, v, 1, positions, page_slot, page_pos, pool_blocks, 32)
    ).lower(sd(jnp.bfloat16, B, 30, 128), pool, pool, sd(jnp.int32, B),
            sd(jnp.int32, 256), sd(jnp.int32, 256),
            sd(jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%paged_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2 ** 20


@pytest.mark.parametrize("B", [16, 64])
def test_grouped_paged_attention_compiles_for_the_chip(one_chip, B):
    """The sparse-expert decoder's full layers (ISSUE 32): 48 query heads
    over 2 layers of 576 + 1 pages of 8 key/value heads x 256 x 128,
    bfloat16, read in place; the smallest and the largest decode bucket."""
    def sd(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sd(jnp.bfloat16, 2, 577, 8, 256, 128)
    compiled = jax.jit(
        lambda q, k, v, positions, page_slot, page_pos, pool_blocks:
        paged_attention.paged_decode_attention(
            q, k, v, 1, positions, page_slot, page_pos, pool_blocks, 32)
    ).lower(sd(jnp.bfloat16, B, 48, 128), pool, pool, sd(jnp.int32, B),
            sd(jnp.int32, 576), sd(jnp.int32, 576),
            sd(jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%paged_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20


@pytest.mark.parametrize("heads,pool,table,T", [
    (48, (2, 577, 8, 256, 128), 576, 256),
    (48, (2, 577, 8, 256, 128), 576, 2048),
    (30, (4, 257, 30, 256, 128), 256, 256),
    (30, (4, 257, 30, 256, 128), 256, 2048),
])
def test_paged_prefill_attention_compiles_for_the_chip(one_chip, heads, pool,
                                                       table, T):
    """The causal prefill kernel (ISSUE 33) over both served pools, the
    smallest and the largest prefill bucket: grouped (48 query heads on 8
    key/value heads, tiles of 128 positions) and ungrouped (30 on 30, tiles
    of 512), the pools read in place."""
    def sd(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, k, v, page_row, start:
        paged_attention.paged_prefill_attention(q, k, v, 1, page_row, start)
    ).lower(sd(jnp.bfloat16, T, heads, 128), sd(jnp.bfloat16, *pool),
            sd(jnp.bfloat16, *pool), sd(jnp.int32, table),
            sd(jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%paged_prefill_attention" in text
    # The queries' and the context's re-laying, never a pool or a score.
    assert compiled.memory_analysis().temp_size_in_bytes < 128 * 2 ** 20


@pytest.mark.parametrize("tokens,tile", [(16, 16), (64, 16), (512, 64),
                                         (2048, 128)])
def test_expert_layer_compiles_for_the_chip_at_the_served_size(one_chip,
                                                               tokens, tile):
    """The grouped expert product of ``ops/moe.py``: 128 held experts of
    [3072, 2 x 1024] and [1024, 3072] bfloat16, 10 experts a token, for
    decode batches and prefill chunks; the tile follows the pairs an expert
    sees. The profile's event carries the kernel's name and the expert
    matrices' shapes: what ``benchmark/reduce/kinds/trace_moe_roofline.py``
    reads."""
    from vilbert_multitask_tpu.ops import moe

    def sd(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert moe.tile_rows(tokens * 10, 128) == tile
    compiled = jax.jit(
        lambda x, experts, weights, gate_up, down, real: moe.experts_forward(
            x, experts, weights, (0, 128), gate_up, down, real)
    ).lower(sd(jnp.float32, tokens, 3072), sd(jnp.int32, tokens, 10),
            sd(jnp.float32, tokens, 10), sd(jnp.bfloat16, 128, 3072, 2048),
            sd(jnp.bfloat16, 128, 1024, 3072), sd(jnp.bool_, tokens)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%moe_experts" in text
    assert "bf16[128,3072,2048]" in text and "bf16[128,1024,3072]" in text


@pytest.mark.parametrize("B,heads,ring", [
    (32, 40, (8, 129, 10, 512, 128)),      # phi4flash: 4 rows a key pair
    (128, 40, (8, 129, 10, 512, 128)),
    (64, 72, (3, 65, 8, 512, 128)),        # laguna: 9 query heads a head
])
def test_ring_decode_attention_compiles_for_the_chip(one_chip, B, heads,
                                                     ring):
    """A decode step's attention over the slots' rings (ISSUE 34), read in
    place: no copy of a ring array (2.7 GB at the larger served size), of a
    layer of it, or of the scores."""
    def sd(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, k, v, positions: paged_attention.ring_decode_attention(
            q, k, v, 1, positions, 512)
    ).lower(sd(jnp.bfloat16, B, heads, 128), sd(jnp.bfloat16, *ring),
            sd(jnp.bfloat16, *ring), sd(jnp.int32, B)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%ring_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20


@pytest.mark.parametrize("rows,prefill", [(2048, True), (256, True),
                                           (24, False), (8, False)])
def test_cca_prep_compiles_for_the_chip(one_chip, rows, prefill):
    """CCA's preparation of queries and keys at the served width
    (8 query and 2 key/value heads of 128, half of each rotated): a prefill
    chunk's window in blocks that overlap by the halo, and a decode step's
    rows. The profile's event carries the kernel's name, the second
    convolution's matrices and the output's width: what
    ``benchmark/reduce/kinds/trace_cca_prep_roofline.py`` reads."""
    from vilbert_multitask_tpu.ops import cca

    def sd(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    dims = dict(heads=8, kv_heads=2, rotary_dim=64)
    maps = (sd(jnp.bfloat16, 2, 1280), sd(jnp.bfloat16, 2, 10, 128, 128),
            sd(jnp.bfloat16, 2), sd(jnp.float32, rows, 128),
            sd(jnp.float32, rows, 128))
    if prefill:
        compiled = jax.jit(lambda tail, c, *m: cca.cca_prep_prefill(
            tail, c, *m, **dims)).lower(
                sd(jnp.float32, 2, 1280), sd(jnp.float32, rows, 1280),
                *maps).compile()
    else:
        compiled = jax.jit(lambda a, b, c, *m: cca.cca_prep_rows(
            a, b, c, *m, **dims)).lower(
                *[sd(jnp.float32, rows, 1280)] * 3, *maps).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%cca_prep" in text
    assert "bf16[2,10,128,128]" in text and f"bf16[{rows},1024]" in text


def test_ring_store_writes_in_place_on_the_chip(one_chip):
    """One slot's ring stored into the donated array: the array is aliased
    to the result, never copied."""
    def sd(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda ring, slot, new: paged_attention.ring_store(ring, 1, slot,
                                                           new),
        donate_argnums=(0,)
    ).lower(sd(jnp.bfloat16, 8, 129, 10, 512, 128), sd(jnp.int32),
            sd(jnp.bfloat16, 10, 512, 128)).compile()
    assert "%ring_store" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 4 * 2 ** 20
    assert memory.alias_size_in_bytes >= 8 * 129 * 10 * 512 * 128 * 2


@pytest.mark.parametrize("B", [32, 128])
def test_pair_rows_over_the_one_pool_compile_for_the_chip(one_chip, B):
    """Differential attention's 40 query rows (both maps of 20 pairs, zero-
    padded to the pair's 128 lanes) over the one paged layer of 640 + 1
    pages of 10 key pairs x 256 x 128: what the full layer and every cross
    layer run in a decode step (ISSUE 34)."""
    def sd(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sd(jnp.bfloat16, 1, 641, 10, 256, 128)
    compiled = jax.jit(
        lambda q, k, v, positions, page_slot, page_pos, pool_blocks:
        paged_attention.paged_decode_attention(
            q, k, v, 0, positions, page_slot, page_pos, pool_blocks, 32)
    ).lower(sd(jnp.bfloat16, B, 40, 128), pool, pool, sd(jnp.int32, B),
            sd(jnp.int32, 640), sd(jnp.int32, 640),
            sd(jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%paged_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20


@pytest.mark.parametrize("T", [256, 2048])
def test_selective_scan_compiles_for_the_chip(one_chip, T):
    """5,120 channels, state 16, the smallest and the largest prefill
    bucket: what the interpreter cannot show (tiling, SMEM, VMEM) the
    chip's compiler refuses here."""
    Ci, N = 5120, 16

    def sd(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(selective_scan.selective_scan).lower(
        sd(T, Ci), sd(T, Ci), sd(T, N), sd(T, N), sd(N, Ci), sd(Ci),
        sd(N, Ci)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The profile's event carries the kernel's name:
    # benchmark/reduce/kinds/trace_selective_scan_roofline.py finds it so.
    assert "%selective_scan" in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_phi4flash_decode_program_updates_its_state_in_place(one_chip):
    """The whole decode program of the smallest bucket at the served size
    (ISSUE 34; 15 s): no rematerialised update (where a later layer's read
    of its state was fused into a reader of the whole updated array, the
    compiler recomputed the update of the layer before and, in place,
    applied it twice: the served logits drifted from the second decode step
    on, on the chip only, and only with the memory near its limit), and no
    copy of a ring array or of the scan state."""
    from vilbert_multitask_tpu.config import GenerateConfig, Phi4FlashConfig
    from vilbert_multitask_tpu.engine.seqstate import SequenceState
    from vilbert_multitask_tpu.models import phi4flash

    cfg, B, pages = Phi4FlashConfig(), 32, 640
    gen = GenerateConfig(model=cfg, slots=128, kv_pages=pages,
                         decode_buckets=(32, 64, 96, 128))
    st = SequenceState(gen, phi4flash.state_layout(cfg, "bfloat16"))

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda s: sd(s, "bfloat16"), phi4flash.param_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple))
    state = {n: sd(s, st.layout.slot_arrays[n].dtype)
             for n, s in st.slot_shapes.items()}
    state.update(k=sd(st.pool_shape, "bfloat16"),
                 v=sd(st.pool_shape, "bfloat16"), token=sd((128,), "int32"))
    x = {"active": sd((B,), "bool"), "positions": sd((B,), "int32"),
         "write_page": sd((B,), "int32"), "page_slot": sd((pages,), "int32"),
         "page_pos": sd((pages,), "int32"), "pool_blocks": sd((), "int32"),
         "logit_ids": sd((B, 16), "int32")}

    def run(params, state, x):
        return phi4flash.decode_step(
            cfg, params, state, x["active"], x["positions"], x["write_page"],
            x["page_slot"], x["page_pos"], x["pool_blocks"], x["logit_ids"],
            attention_block=32)

    compiled = jax.jit(run, donate_argnums=(1,)).lower(params, state,
                                                      x).compile()
    text = compiled.as_text()
    assert ".remat" not in text
    copies = [line for line in text.splitlines() if " copy(" in line]
    assert not any("[8,129,10,512,128]" in line or "[9,128,16,5120]" in line
                   for line in copies)
    assert compiled.memory_analysis().temp_size_in_bytes < 128 * 2 ** 20


SLAB_ROWS = 1 + 7680 + 32  # the served slab: pad slot, cache, scratch


def slab_program(gather):
    """A rows program's read of the served slab and a consumer like the
    model's visual embedding: a 2048 -> 1024 projection of the features,
    the boxes' own, a LayerNorm and the mask."""
    def run(features, spatials, image_mask, rows, w, w_box):
        f, s, m = (gather(x, rows) for x in (features, spatials, image_mask))
        h = jnp.einsum("brf,fh->brh", f, w,
                       preferred_element_type=jnp.float32)
        h = h + jnp.einsum("brs,sh->brh", s, w_box)
        h = (h - h.mean(-1, keepdims=True)) * jax.lax.rsqrt(
            h.var(-1, keepdims=True) + 1e-12)
        return h * m[..., None]
    return run


def whole_slab_results(gather, bucket, one_chip):
    """The compiled program's instructions, other than its parameters,
    whose result holds the whole ``features`` leaf."""
    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    text = jax.jit(slab_program(gather)).lower(
        sd((SLAB_ROWS, 101, 2048), "bfloat16"),
        sd((SLAB_ROWS, 101, 5), "float32"),
        sd((SLAB_ROWS, 101), "int32"), sd((bucket,), "int32"),
        sd((2048, 1024), "bfloat16"), sd((5, 1024), "float32"),
    ).compile().as_text()
    # An instruction reads "%name = <result> opcode(%operand, ...)": the
    # result is what comes before the first operand.
    results = [line.split(" = ", 1)[1].split("%", 1)[0]
               for line in text.splitlines() if " = " in line]
    return [r for r in results if f"bf16[{SLAB_ROWS},101,2048]" in r
            and "parameter(" not in r]


@pytest.mark.parametrize("bucket", [2, 10, 32])
def test_rows_program_reads_the_slab_in_place(one_chip, bucket):
    """The rows program's row-by-row read (``engine/runtime.py:
    _gather_rows``) of the served slab (7713 rows of 101 x 2048 bfloat16,
    3.19 GB) compiles with no instruction that makes a whole copy of it:
    such a copy took three quarters of the device's busy time in the
    saturated ViLBERT cell."""
    from vilbert_multitask_tpu.engine.runtime import _gather_rows

    assert whole_slab_results(_gather_rows, bucket, one_chip) == []


def test_a_gather_of_ten_rows_re_lays_the_whole_slab(one_chip):
    """What the row-by-row read replaced: ``slab[k][rows]`` of ten rows
    wants the features in another tiling and copies all of them first. If
    a compiler stops doing so, this case says it, and the other form may
    go back to the plain gather."""
    copies = whole_slab_results(lambda x, rows: x[rows], 10, one_chip)
    assert any(" copy(" in r or "copy-start(" in r for r in copies), copies
