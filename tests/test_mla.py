"""``ops/mla.py``'s decode attention in its two forms at the published head
count and latent width (16 heads, 512 + 64 channels): the kernel
(``_token_attention``, run here under Pallas' TPU interpret mode) against the
plain ``jax.numpy`` form (what ``mla_token`` picks off the TPU), over ragged
positions and a span that is no whole number of blocks.  Both take bfloat16
operands and keep float32 scores, softmax statistics and sums, so they differ
by rounding order only: the probabilities go to bfloat16 before the sum in
both, unnormalised in the kernel, so the outputs differ by a bfloat16 ulp or
two (2**-7 of the largest; one slot seen too many moves an output by more
than twice that).  And which form is picked, by the backend and the shapes
alone, and the slots each reads."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from sparkdl_tpu.ops import mla

#: the published widths (Moonlight-16B-A3B) with a small hidden size
SHAPE = mla.Shape(heads=16, nope=128, rope=64, v=128, rank=512,
                  theta=50000.0, kv_eps=1e-6, scale=192 ** -0.5)
HIDDEN = 64
#: a position at 0, on a block's last slot, on a block's first, inside, and
#: on the span's last; the spans: 2.25 blocks of 512 (the last one moved back
#: to end with the span), and less than one block
CASES = {1152: [0, 511, 512, 700, 1151], 384: [0, 127, 128, 200, 383]}
TOLERANCE = 2 ** -7


_interpreted_kernel = functools.partial(
    mla._token_attention, interpret=pltpu.InterpretParams())


def _layer_params(seed):
    rng = np.random.default_rng(seed)

    def normal(*shape, std=0.05):
        return jnp.asarray(std * rng.normal(size=shape), jnp.bfloat16)

    heads = SHAPE.heads
    return {
        # a peaked softmax, as a trained model's (PERF.md section 2)
        "wq": normal(HIDDEN, heads * (SHAPE.nope + SHAPE.rope), std=0.3),
        "wkva": normal(HIDDEN, SHAPE.latent, std=0.2),
        "kv_norm": jnp.ones((SHAPE.rank,), jnp.bfloat16),
        "wkvb": normal(SHAPE.rank, heads * (SHAPE.nope + SHAPE.v)),
    }


def _latent(span, rows, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(2, rows, span, SHAPE.latent)),
                       jnp.bfloat16)


@pytest.mark.parametrize("span", sorted(CASES))
def test_the_kernel_is_the_plain_form_over_ragged_positions(span):
    """``softmax . [c | k_pe]`` of both forms against one leaf: every row's
    blocks up to its position, the last one's overlap with the block before
    masked, slots past the position left out.  The kernel is handed each
    row's slot as the leaf holds it, and gives the leaf back as it was."""
    rows = len(CASES[span])
    rng = np.random.default_rng(span)
    query = jnp.asarray(0.1 * rng.normal(size=(rows, SHAPE.heads,
                                               SHAPE.latent)), jnp.bfloat16)
    latent = _latent(span, rows, seed=1)
    position = jnp.asarray(CASES[span], jnp.int32)
    block = min(mla.TOKEN_BLOCK, span)
    got, leaf = _interpreted_kernel(
        query, latent[1, jnp.arange(rows), position], latent, jnp.int32(1),
        position, SHAPE.scale, block)
    np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                  np.asarray(latent, np.float32))
    want = mla._token_attention_plain(query, latent, jnp.int32(1), position,
                                      SHAPE.scale)
    assert got.dtype == want.dtype == jnp.bfloat16
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    size = np.abs(want).max(axis=(1, 2))  # a row's largest
    assert (np.abs(got - want).max(axis=(1, 2)) < TOLERANCE * size).all()
    # the tolerance sees one slot too many: the next slot seen as well moves
    # every row (but the last, at the span's end) by more than twice it
    past = mla._token_attention_plain(
        query, latent, jnp.int32(1), jnp.minimum(position + 1, span - 1),
        SHAPE.scale)
    moved = np.abs(np.asarray(past, np.float32) - want).max(axis=(1, 2))
    assert (moved[:-1] > 2 * TOLERANCE * size[:-1]).all()


#: (span, positions) of the kernel's own write besides CASES: a lane tile's
#: last and first slot (127, 128) in a span of several blocks, the first
#: slot of the moved-back last block of 2.25 (1,024: its lane 384) and one
#: inside it; and more rows than a tile of rows (the kernel takes each row's
#: new latent from its tile of 128 rows), their positions wrapping the span
WRITES = {
    "1152": (1152, CASES[1152]), "384": (384, CASES[384]),
    "tile-edges-and-moved-back": (1152, [127, 128, 0, 1024, 1100]),
    "rows-past-a-tile": (384, [(7 * i) % 384 for i in range(130)]),
}


@pytest.mark.parametrize("case", sorted(WRITES))
def test_mla_token_writes_and_attends_alike_on_both_branches(
        case, monkeypatch):
    """``mla_token`` whole, plain here (``write_positions``, then the plain
    form) and then on the kernel's branch, where the kernel writes each
    row's new latent itself: the leaf as written is the same to the bit,
    every other slot, row and layer keeps its bytes, the new slots read
    back, and the output is the same within bfloat16 rounding."""
    span, positions = WRITES[case]
    rows = len(positions)
    lp = _layer_params(seed=4)
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.normal(size=(rows, HIDDEN)), jnp.bfloat16)
    latent = _latent(span, rows, seed=6)
    position = jnp.asarray(positions, jnp.int32)
    assert mla.token_block(SHAPE.latent, span) == 0
    want, want_leaf = mla.mla_token(lp, u, latent, jnp.int32(1), position,
                                    SHAPE)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert mla.token_block(SHAPE.latent, span) == min(mla.TOKEN_BLOCK, span)
    ran = []

    def kernel(*args):
        ran.append(args[-1])
        return _interpreted_kernel(*args)

    monkeypatch.setattr(mla, "_token_attention", kernel)
    monkeypatch.setattr(mla, "write_positions", None)  # not on this branch
    got, leaf = mla.mla_token(lp, u, latent, jnp.int32(1), position, SHAPE)
    assert ran == [min(mla.TOKEN_BLOCK, span)]
    leaf, before = np.asarray(leaf, np.float32), np.asarray(latent, np.float32)
    np.testing.assert_array_equal(leaf, np.asarray(want_leaf, np.float32))
    # the new slots read back, and nothing else moved
    _, _, new = mla.project(lp, u, position, SHAPE)
    written = (1, np.arange(rows), np.asarray(position))
    np.testing.assert_array_equal(leaf[written], np.asarray(new, np.float32))
    leaf[written] = before[written]
    np.testing.assert_array_equal(leaf, before)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    size = np.abs(want).max(axis=1)  # a row's largest
    assert (np.abs(got - want).max(axis=1) < 2 * TOLERANCE * size).all()


@pytest.mark.parametrize("backend,width,span,block,read", [
    # the cell's: 58 lane tiles, 36 sublane tiles; blocks 1, 1, 2 and 15
    ("tpu", 576, 7424, 512, 19 * 512),
    ("tpu", 576, 384, 384, 4 * 384),  # a span under a block is one block
    ("tpu", 48, 640, 512, 6 * 512),   # the tests' width: 3 tiles of 16
    ("tpu", 40, 640, 0, 4 * 640),     # a width of no whole tiles
    ("tpu", 576, 7400, 0, 4 * 7400),  # a span of no whole lane tiles
    ("cpu", 576, 7424, 0, 4 * 7424)],
    ids=["tpu-cell", "tpu-short", "tpu-narrow", "tpu-ragged-width",
         "tpu-ragged-span", "cpu"])
def test_the_kernel_is_picked_by_backend_and_shapes_alone(
        monkeypatch, backend, width, span, block, read):
    """``token_block`` is what ``mla_token`` asks and what the decode span's
    ``slots_read`` counts by: whole blocks up to each position where the
    kernel runs, the whole span where the plain form does."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert mla.token_block(width, span) == block
    positions = np.array([[0, 511, 512, span - 1]]) % span
    assert mla.token_slots_read(positions, width, span) == read


# -- the prefill's fold -------------------------------------------------------

#: segments of 32 positions against a span of 320 slots in blocks of 128:
#: pair 0 at start 0; pairs 1 and 2 consecutive segments of row 1 in one
#: call; pair 3 a row past the last (the last is read) ending with the span,
#: whose third block is moved back to end with it
SEGMENT = dict(n=32, span=320, block=128, rows=[0, 1, 1, 7],
               starts=[0, 64, 96, 288])


@pytest.mark.parametrize("heads,group,masked", [
    (4, 4, False), (4, 4, True), (16, 4, False), (16, 4, True)],
    ids=["one-group", "one-group-chosen", "four-groups",
         "four-groups-chosen"])
def test_the_prefill_kernel_is_the_plain_fold(heads, group, masked):
    """``softmax . [c | k_pe]`` of the kernel (under Pallas' TPU interpret
    mode) against the plain fold over one float32 leaf, at the published
    latent width: the same to float32 rounding in the ``rank`` channels the
    kernel sums, and zero in the rope channels it leaves out."""
    n, span, block = SEGMENT["n"], SEGMENT["span"], SEGMENT["block"]
    c = len(SEGMENT["starts"])
    rng = np.random.default_rng(heads + masked)
    query = jnp.asarray(0.1 * rng.normal(size=(c, n, heads, SHAPE.latent)),
                        jnp.float32)
    latent = jnp.asarray(rng.normal(size=(2, 3, span, SHAPE.latent)),
                         jnp.float32)
    rows = jnp.asarray(SEGMENT["rows"], jnp.int32)
    start = jnp.asarray(SEGMENT["starts"], jnp.int32)
    chosen = None
    if masked:
        kept = rng.random((c, n, span)) < 0.4
        kept[:, :, 0] = True  # every query keeps a slot it sees
        chosen = jnp.asarray(kept, jnp.bfloat16)
    got = mla._segment_attention(
        query.transpose(0, 2, 1, 3), latent, jnp.int32(1), rows, start,
        SHAPE.scale, SHAPE.rank, block, group, chosen,
        interpret=pltpu.InterpretParams())
    want = mla._segment_attention_plain(query, latent, jnp.int32(1), rows,
                                        start, SHAPE.scale, block, chosen)
    assert got.shape == want.shape == (c, heads, n, SHAPE.latent)
    got, want = np.asarray(got), np.asarray(want)
    assert not got[..., SHAPE.rank:].any()
    np.testing.assert_allclose(got[..., :SHAPE.rank], want[..., :SHAPE.rank],
                               rtol=1e-5, atol=1e-5)
    # the tolerance sees one slot too many: the pair at the span's end with
    # the slot before its segment masked reads otherwise
    if not masked:
        hidden = jnp.asarray(np.arange(span) != 287, jnp.bfloat16)
        fewer = mla._segment_attention_plain(
            query, latent, jnp.int32(1), rows, start, SHAPE.scale, block,
            jnp.broadcast_to(hidden, (c, n, span)))
        assert np.abs(np.asarray(fewer)[3] - want[3]).max() > 1e-3


@pytest.mark.parametrize("backend,heads,n,width,span,group", [
    ("tpu", 64, 128, 576, 30848, 32),  # GLM-5's cell: two groups of 32
    ("tpu", 16, 128, 576, 7424, 16),   # Moonlight's: one group
    ("tpu", 16, 16, 576, 640, 16),     # a short segment: every head
    ("tpu", 24, 256, 576, 640, 12),    # a divisor of the heads
    ("tpu", 64, 128, 584, 30848, 0),   # a width of no whole tiles
    ("tpu", 64, 128, 576, 30800, 0),   # a span of no whole lane tiles
    ("tpu", 4, 8, 576, 640, 0),        # a segment of no whole tiles
    ("cpu", 64, 128, 576, 30848, 0)],
    ids=["tpu-glm5", "tpu-moonlight", "tpu-short", "tpu-divisor",
         "tpu-ragged-width", "tpu-ragged-span", "tpu-ragged-segment", "cpu"])
def test_the_prefill_kernel_is_picked_by_backend_and_shapes_alone(
        monkeypatch, backend, heads, n, width, span, group):
    """``segment_group`` is what ``segment_attention`` asks: the heads of a
    grid step where the kernel runs, 0 (the plain fold) elsewhere."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    shape = SHAPE._replace(heads=heads, rank=width - SHAPE.rope)
    assert mla.segment_group(shape, n, span) == group
