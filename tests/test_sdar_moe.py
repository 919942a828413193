"""SDAR-MoE (``sparkdl_tpu/models/sdar_moe.py``, ``sparkdl_tpu/ops/moe.py``)
against the plain reference (``chipbench/reference/sdar_moe.py``: float32,
no cache, one row at a time, a loop over experts) at a tiny size on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import sdar_moe as reference
from sparkdl_tpu.models import sdar_moe
from sparkdl_tpu.ops import moe
from sparkdl_tpu.ops.moe import gmm_grouped_dot, moe_ffn, route

CONFIG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=32, norm_topk_prob=True,
    rope_theta=1e6, rms_norm_eps=1e-6,
)
CFG = sdar_moe.SdarMoeConfig.from_dict(CONFIG)
BLOCK, MASK = 4, 255
# granite's operating point in small: k = 10, no multiple of a tile's 8 rows,
# and half of the experts held elsewhere
WIDE = dict(CONFIG, num_experts=20, num_experts_per_tok=10)


@pytest.fixture(scope="module")
def params():
    return reference.make_params(CONFIG, 2**31 + 77, "float32")


@pytest.fixture(scope="module")
def wide_params():
    return reference.make_params(WIDE, 2**31 + 78, "float32")


@pytest.fixture(params=[False, True], ids=["one-gather", "slabs"])
def slabs(request, monkeypatch):
    """Both ways the k parts come back: by one gather under
    ``moe._SLAB_ROWS`` tokens, by one gather a slab from there on."""
    if request.param:
        monkeypatch.setattr(moe, "_SLAB_ROWS", 1)


def _layer(params, index=0):
    return {k: v[index] for k, v in params["layers"].items()}


def _skewed(lp, starved=5):
    """A router that favours expert 1 and never picks ``starved``."""
    router = np.array(lp["router"])
    router[:, 1] += 0.3
    router[:, starved] = 0.0
    router[0, starved] = -50.0  # after the norm x[0] decides; see _tokens
    return dict(lp, router=jnp.asarray(router))


def _tokens(n=24, seed=3):
    x = np.random.default_rng(seed).normal(size=(n, 64)).astype(np.float32)
    x[:, 0] = np.abs(x[:, 0]) + 1.0  # so that the starved expert's logit is low
    return jnp.asarray(x)


def _experts(lp, lo=0, hi=8):
    return {k: lp[k][lo:hi] for k in ("w_gate", "w_up", "w_down")}


def _held_part(config, lp, x, held, stack=None, index=0):
    """(``moe_ffn``'s part, its counts, the reference's part) for the experts
    of ``held``; with ``stack`` through the stacked weights and the index, as
    a scan over layers calls it."""
    lo, hi = held
    want = reference.moe(config, dict(lp, **_experts(lp, lo, hi)), x,
                         experts_held=held)
    k = config["num_experts_per_tok"]
    if stack is None:
        got, counts = moe_ffn(x, lp["router"], _experts(lp, lo, hi), top_k=k,
                              experts_held=held)
    else:
        share = {name: w[:, lo:hi] for name, w in stack.items()}
        got, counts = jax.jit(lambda i: moe_ffn(
            x, lp["router"], share, top_k=k, experts_held=held,
            stack_index=i))(jnp.int32(index))
    return got, counts, want


def _stack(params):
    return {k: params["layers"][k] for k in ("w_gate", "w_up", "w_down")}


@pytest.mark.parametrize("top_k, held, tokens, stacked", [
    (1, None, 24, False), (2, None, 24, False), (4, None, 24, False),
    (10, (0, 10), 21, False), (10, (10, 20), 21, True), (10, None, 24, True),
], ids=["1", "2", "4", "10-low-half-21-tokens", "10-high-half-stacked",
        "10-all-stacked"])
def test_moe_layer_equals_the_loop_over_experts(
        params, wide_params, slabs, top_k, held, tokens, stacked):
    wide = top_k == 10
    config = WIDE if wide else dict(CONFIG, num_experts_per_tok=top_k)
    source = wide_params if wide else params
    lp, x = _skewed(_layer(source, 1 if stacked else 0)), _tokens(tokens)
    got, counts, want = _held_part(
        config, lp, x, held or (0, config["num_experts"]),
        _stack(source) if stacked else None, 1)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert int(counts.sum()) == x.shape[0] * top_k  # nothing dropped
    # uneven loads and an expert with no token at all (ten of twenty
    # experts a token leave the favoured one short of the maximum)
    assert int(counts[5]) == 0
    assert int(counts[1]) == counts.max() or wide and counts[1] > counts.mean()
    _, ref_counts = reference.route(config, lp, x, None)
    np.testing.assert_array_equal(counts, ref_counts)


def test_router_renormalises_its_top_k_and_breaks_ties_low(params):
    x = _tokens(6)
    router = jnp.zeros((64, 8))  # every expert ties
    weights, experts = route(x, router, 3)
    np.testing.assert_array_equal(experts, np.tile([0, 1, 2], (6, 1)))
    np.testing.assert_allclose(weights, 1 / 3, rtol=1e-6)
    weights, _ = route(x, _layer(params)["router"], 3, norm_topk=False)
    assert float(weights.sum(-1).max()) < 1.0


@pytest.mark.parametrize("cut, n_experts, stacked", [
    (4, 8, False), (2, 8, False), (7, 8, False),
    (10, 20, False), (10, 20, True), (3, 20, True),
], ids=["4", "2", "7", "10-of-20", "10-of-20-stacked", "3-of-20-stacked"])
def test_the_shares_add_up_to_the_uncut_layer(
        params, wide_params, slabs, cut, n_experts, stacked):
    """``experts_held`` = (0, cut) and (cut, E), summed, equal the reference's
    whole layer: what expert parallelism asks of the layer."""
    config, source, tokens = (
        (CONFIG, params, 24) if n_experts == 8 else (WIDE, wide_params, 21))
    top_k = config["num_experts_per_tok"]
    lp, x = _skewed(_layer(source, 1)), _tokens(tokens, seed=4)
    whole = reference.moe(config, lp, x)
    parts = []
    for held in ((0, cut), (cut, n_experts)):
        part, counts, want = _held_part(
            config, lp, x, held, _stack(source) if stacked else None, 1)
        assert int(counts.sum()) == top_k * tokens  # routed over ALL experts
        np.testing.assert_allclose(part, want, atol=2e-6)
        parts.append(part)
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=2e-6)


def test_rows_behind_the_last_group_never_reach_the_result(
        wide_params, slabs, monkeypatch):
    """The grouped product leaves the rows behind its last group undefined
    (the pairs of experts held elsewhere sort there): NaN planted in them
    must not show, and NaN times a zero weight is NaN, so they are masked."""
    lp, x = _skewed(_layer(wide_params)), _tokens(21)
    held = (5, 15)
    want, want_counts, _ = _held_part(WIDE, lp, x, held)
    poisoned = []

    def grouped_dot(rows, weights, sizes):
        out = jax.lax.ragged_dot(rows, weights, sizes)
        behind = jnp.arange(rows.shape[0]) >= jnp.sum(sizes)
        poisoned.append(int(behind.sum()))
        return jnp.where(behind[:, None], jnp.nan, out)

    monkeypatch.setattr(moe, "grouped_dot", grouped_dot)
    got, counts, _ = _held_part(WIDE, lp, x, held)
    assert len(poisoned) == 3 and min(poisoned) > 21  # gate, up and down
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(counts, want_counts)


@pytest.mark.parametrize("shuffle", ["reversed", "by-token"])
def test_counts_and_result_do_not_depend_on_the_pair_order(
        wide_params, slabs, shuffle, monkeypatch):
    """A token's k choices in another order number the pairs differently
    and add the k parts in another order; the counts are the same and the
    result is the dense loop over the experts', to float32's last bits."""
    lp, x = _skewed(_layer(wide_params, 1)), _tokens(21, seed=9)
    held = (0, 10)
    rng = np.random.default_rng(5)
    columns = (np.tile(np.arange(10)[::-1], (21, 1)) if shuffle == "reversed"
               else np.stack([rng.permutation(10) for _ in range(21)]))

    def shuffled_route(*args, **kwargs):
        weights, experts = route(*args, **kwargs)
        return (jnp.take_along_axis(weights, columns, axis=1),
                jnp.take_along_axis(experts, columns, axis=1))

    plain, plain_counts, want = _held_part(WIDE, lp, x, held)
    monkeypatch.setattr(moe, "route", shuffled_route)
    got, counts, _ = _held_part(WIDE, lp, x, held, _stack(wide_params), 1)
    np.testing.assert_array_equal(counts, plain_counts)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(got, plain, atol=1e-6)


def test_a_stack_of_layers_is_used_through_its_index(params):
    """The scan hands ``moe_ffn`` every layer's experts and an index."""
    x, stack = _tokens(), _stack(params)
    for index in (0, 1):
        lp = _layer(params, index)
        want, _ = moe_ffn(x, lp["router"], _experts(lp), top_k=2)
        got, _ = jax.jit(
            lambda i: moe_ffn(x, lp["router"], stack, top_k=2, stack_index=i)
        )(jnp.int32(index))
        np.testing.assert_allclose(got, want, atol=1e-6)
    with pytest.raises(ValueError, match="experts_held"):
        moe_ffn(x, lp["router"], _experts(lp, 0, 4), top_k=2)


@pytest.mark.parametrize("rows", [48, 42, 7])
def test_the_chips_grouped_product_equals_ragged_dot(rows):
    """The TPU's path (``megablox.gmm``, interpreted here): groups that are
    empty, rows behind the last group, and a row count no tile divides."""
    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.normal(size=(rows, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 64, 32)), jnp.float32)
    sizes = np.zeros(16, np.int32)
    sizes[8:16] = np.array([10, 0, 7, 3, 12, 0, 9, 1]) * rows // 48
    used = int(sizes.sum())
    assert used < rows
    want = jax.lax.ragged_dot(x, w, jnp.asarray(sizes))
    got = gmm_grouped_dot(x, w, jnp.asarray(sizes), interpret=True)
    assert got.shape == want.shape
    np.testing.assert_allclose(got[:used], want[:used], atol=1e-5)


def _rows(seed=0, n=3, length=16):
    rng = np.random.default_rng(seed)
    # one short row: its pads must not be seen; the reference runs on two
    # distinct lengths only (every eager shape is a compile on the CPU)
    return (rng.integers(0, MASK, (n, length)).astype(np.int32),
            np.array([16, 16, 8][:n], np.int32))


def test_whole_model_logits_float32(params):
    tokens, lengths = _rows()
    got = np.asarray(jax.jit(
        lambda p, t, l: sdar_moe.forward_logits(p, CFG, t, l, BLOCK)
    )(params, tokens, lengths))
    for row, n in enumerate(lengths):
        want = np.asarray(reference.forward(
            params, CONFIG, tokens[row, :n], BLOCK))
        assert np.abs(got[row, :n] - want).max() <= 1e-5


def test_whole_model_logits_bfloat16(params):
    """bfloat16 keeps 8 bits: every rounding is 2**-9 relative, and a
    logit (spread ~0.17 here) passes through ~20 of them in two layers, so
    0.02 of the spread holds with room; float8 would read ~0.1."""
    tokens, lengths = _rows(1)
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    exact = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), low)
    got = np.asarray(jax.jit(
        lambda p, t, l: sdar_moe.forward_logits(p, CFG, t, l, BLOCK)
    )(low, tokens, lengths))
    for row, n in enumerate(lengths):
        want = np.asarray(reference.forward(
            exact, CONFIG, tokens[row, :n], BLOCK))
        gap = np.abs(got[row, :n] - want).max() / want.std()
        assert gap < 0.05, gap


def _prefilled(params, tokens, whole, length, span=32):
    """The cache [L, r, KV, span, dh] with the prompts' whole blocks in."""
    k, v, _ = sdar_moe.prefill(
        params, CFG, tokens[:, :length], jnp.asarray(whole), BLOCK)
    shape = (2, len(whole), 2, span, 16)
    return (jnp.zeros(shape).at[:, :, :, :length].set(k),
            jnp.zeros(shape).at[:, :, :, :length].set(v))


@pytest.mark.parametrize("rest", [0, 1, 3])
def test_block_causal_attention_through_the_cache(params, rest):
    """Prefill of the whole blocks, then one block forward against the
    cache, give the reference's full no-cache forward at the block."""
    tokens, _ = _rows(2)
    whole = np.array([12, 12, 4], np.int32)
    cache_k, cache_v = _prefilled(params, tokens, whole, 12)
    base = 16
    block = np.full((3, BLOCK), MASK, np.int32)
    for row in range(3):
        block[row, :rest] = tokens[row, whole[row]:whole[row] + rest]
    logits, *_ = sdar_moe._block_forward(
        params, CFG, cache_k, cache_v, jnp.asarray(whole), jnp.asarray(whole),
        jnp.array([base, base], jnp.int32), jnp.asarray(block))
    for row in range(3):
        sequence = list(tokens[row, :whole[row]]) + list(block[row])
        want = reference.forward(
            params, CONFIG, sequence, BLOCK,
            range(whole[row], whole[row] + BLOCK))
        np.testing.assert_allclose(logits[row], want, atol=1e-5)


def test_a_committed_block_is_seen_by_the_next(params):
    """A plain step, then a chained one that commits the first block on its
    way, then a forward of a third block with the second pending: what that
    sees through the cache (block 0) and beside itself (block 1) is the
    reference's whole sequence."""
    tokens, _ = _rows(5)
    whole = jnp.array([8, 8, 8], jnp.int32)
    cache_k, cache_v = _prefilled(params, tokens, whole, 8, span=24)
    where = jnp.array([8, 8], jnp.int32)
    unknown = jnp.zeros((3, BLOCK), bool)
    blank = jnp.zeros((3, BLOCK), jnp.int32)
    step = dict(steps=2, mask_id=MASK)
    cache_k, cache_v, start, where, (first, at, _, counts) = sdar_moe.block_step(
        params, CFG, cache_k, cache_v, whole, whole, where, blank, unknown,
        **step)
    assert (np.asarray(at) >= 0).all() and not (np.asarray(first) == MASK).any()
    pairs = 3 * BLOCK * 2 * 2  # of one forward: tokens x layers x experts each
    assert int(counts.sum()) == 2 * pairs
    np.testing.assert_array_equal(where, [8, 12])
    cache_k, cache_v, start, where, (second, _, _, counts) = sdar_moe.block_step(
        params, CFG, cache_k, cache_v, whole, start, where, blank, unknown,
        first, **step)
    assert int(counts.sum()) == 3 * pairs  # the first forward carried two blocks
    np.testing.assert_array_equal(where, [8, 16])
    np.testing.assert_array_equal(start, whole + 2 * BLOCK)
    probe = jnp.full((3, BLOCK), MASK, jnp.int32)
    logits, _, k, _ = sdar_moe._block_forward(
        params, CFG, cache_k, cache_v, whole, start, where, probe, second)
    assert logits.shape == (3, BLOCK, 256) and k.shape == (2, 3, 2, BLOCK, 16)
    for row in range(3):
        n = int(whole[row])
        sequence = list(tokens[row, :n]) + list(np.asarray(first[row])) \
            + list(np.asarray(second[row])) + [MASK] * BLOCK
        want = reference.forward(params, CONFIG, sequence, BLOCK,
                                 range(n + 2 * BLOCK, n + 3 * BLOCK))
        np.testing.assert_allclose(logits[row], want, atol=1e-5)


@pytest.mark.parametrize("rest", [0, 1, 2, 3])
def test_a_chained_step_is_the_commit_and_then_the_step(params, rest):
    """The commit rides the next block's first forward: the entries it
    writes are a separate forward's of the finished block (here the
    whole-sequence prefill's), what the step then fixes is what it fixes
    against a cache that was committed beforehand, and the pending block
    never sees the new one.  The last row is a batch's padding: no prompt,
    one block of known zeros."""
    tokens = np.concatenate([_rows(6)[0], np.zeros((1, 16), np.int32)])
    whole = np.array([8, 8, 4, 0], np.int32)
    cache_k, cache_v = _prefilled(params, tokens, whole, 8)
    first = np.zeros((4, BLOCK), np.int32)
    known = np.zeros((4, BLOCK), bool)
    for row in range(3):
        first[row, :rest] = tokens[row, whole[row]:whole[row] + rest]
        known[row, :rest] = True
    known[3] = True
    base = 16
    step = dict(steps=2, mask_id=MASK)
    args = (jnp.asarray(whole), jnp.asarray(whole),
            jnp.array([base, base], jnp.int32))
    plain_k, plain_v, start, where, (pending, *_) = sdar_moe.block_step(
        params, CFG, cache_k, cache_v, *args, jnp.asarray(first),
        jnp.asarray(known), **step)
    np.testing.assert_array_equal(plain_k, cache_k)  # block 0 writes nothing
    np.testing.assert_array_equal(pending[3], 0)
    blank, unknown = jnp.zeros_like(first), jnp.zeros_like(known)
    got_k, got_v, _, _, got = sdar_moe.block_step(
        params, CFG, plain_k, plain_v, args[0], start, where, blank, unknown,
        pending, **step)

    # the separate commit: the finished block's entries in a forward of
    # the whole sequence so far, row by row at its own position
    sequence = np.zeros((4, 12), np.int32)
    for row in range(4):
        sequence[row, :whole[row]] = tokens[row, :whole[row]]
        sequence[row, whole[row]:whole[row] + BLOCK] = pending[row]
    k, v, _ = sdar_moe.prefill(
        params, CFG, jnp.asarray(sequence), jnp.asarray(whole + BLOCK), BLOCK)
    want_k, want_v = (jnp.stack(
        [e[:, row, :, whole[row]:whole[row] + BLOCK] for row in range(4)], 1)
        for e in (k, v))
    for got_cache, want, before in ((got_k, want_k, plain_k),
                                    (got_v, want_v, plain_v)):
        np.testing.assert_allclose(
            got_cache[:, :, :, base:base + BLOCK], want, atol=1e-6)
        kept = np.ones(32, bool)
        kept[base:base + BLOCK] = False
        np.testing.assert_array_equal(
            got_cache[:, :, :, kept], before[:, :, :, kept])
    committed = (sdar_moe.write_block(plain_k, want_k, base),
                 sdar_moe.write_block(plain_v, want_v, base))
    *_, want = sdar_moe.block_step(
        params, CFG, *committed, args[0], start, where, blank, unknown, **step)
    np.testing.assert_array_equal(got[0], want[0])  # tokens
    np.testing.assert_array_equal(got[1], want[1])  # the step each was fixed at
    np.testing.assert_allclose(got[2], want[2], atol=1e-5)
    assert int(got[3].sum()) == int(want[3].sum()) * 3 // 2

    # other known tokens in the NEW block: the pending block's entries stay
    other = np.random.default_rng(rest).integers(0, MASK, (4, BLOCK))
    other_k, other_v, *_ = sdar_moe.block_step(
        params, CFG, plain_k, plain_v, args[0], start, where,
        jnp.asarray(other, jnp.int32), jnp.ones_like(known), pending, **step)
    np.testing.assert_allclose(other_k, got_k, atol=1e-6)
    np.testing.assert_allclose(other_v, got_v, atol=1e-6)


@pytest.mark.parametrize("steps_left", [1, 2, 3, 4])
def test_a_denoising_step_fixes_what_the_reference_would(steps_left):
    rng = np.random.default_rng(steps_left)
    logits = rng.normal(size=(5, BLOCK, 32)).astype(np.float32)
    logits[0, 2] = logits[0, 1]  # a tie between positions: the lower wins
    logits[1, :, MASK % 32] = 50.0  # the mask token is never predicted
    masked = rng.random((5, BLOCK)) < 0.7
    masked[0] = True
    tokens = np.where(masked, MASK % 32, 7).astype(np.int32)
    new, still, fixed, logprob = sdar_moe.fix_most_confident(
        jnp.asarray(logits), jnp.asarray(tokens), jnp.asarray(masked),
        steps_left, MASK % 32)
    for row in range(5):
        logp = reference.log_probs(jnp.asarray(logits[row]), MASK % 32)
        want, want_tokens = reference.choose(logp, list(masked[row]), steps_left)
        assert sorted(np.flatnonzero(fixed[row])) == want
        assert [int(new[row, i]) for i in want] == want_tokens
        np.testing.assert_allclose(
            np.asarray(logprob[row])[want], logp.max(-1)[want], atol=1e-5)
        np.testing.assert_array_equal(still[row], masked[row] & ~fixed[row])


def test_config_from_the_published_keys_and_param_shapes():
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "chipbench",
                        "configs", "sdar_30b_a3b-blockdiffusion.json")
    with open(path) as fh:
        published = json.load(fh)
    cfg = sdar_moe.SdarMoeConfig.from_dict(published)
    assert (cfg.hidden_size, cfg.num_experts, cfg.held) == (2048, 128, (0, 128))
    shapes = sdar_moe.param_shapes(cfg)
    assert shapes == reference.shapes(published)
    leaves = jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    assert sum(int(np.prod(s)) for s in leaves) == 4_361_055_744
    tiny = sdar_moe.init_params(CFG, 1, jnp.float32)
    assert jax.tree_util.tree_map(lambda a: a.shape, tiny) == \
        sdar_moe.param_shapes(CFG)


# -- sigmoid scoring with a selection bias (PR 37) --------------------------

def _softmax_route_as_it_was(x, router, top_k, norm_topk=True):
    """``ops/moe.route`` as PR 36 left it, to the letter."""
    logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


@pytest.mark.parametrize("norm_topk", [True, False])
def test_the_softmax_path_is_what_it_was_to_the_bit(norm_topk):
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(33, 16)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 12)), jnp.float32)
    was = _softmax_route_as_it_was(x, router, 3, norm_topk)
    for got in (route(x, router, 3, norm_topk),
                route(x, router, 3, norm_topk, scoring="softmax")):
        np.testing.assert_array_equal(got[0], was[0])
        np.testing.assert_array_equal(got[1], was[1])
    # and the same program: SDAR's and Granite's executables do not change
    assert str(jax.make_jaxpr(lambda a, b: route(a, b, 3, norm_topk))(
        x, router)) == str(jax.make_jaxpr(
            lambda a, b: _softmax_route_as_it_was(a, b, 3, norm_topk))(
                x, router))
    with pytest.raises(ValueError, match="softmax"):
        route(x, router, 3, select_bias=jnp.zeros((12,)))
    with pytest.raises(ValueError, match="scoring"):
        route(x, router, 3, scoring="tanh")


def test_sigmoid_routing_selects_by_score_plus_bias_and_weighs_by_score():
    rng = np.random.default_rng(5)
    x = rng.normal(size=(40, 16)).astype(np.float32)
    router = rng.normal(size=(16, 12)).astype(np.float32)
    # a bias large enough to change who is chosen for most tokens
    bias = rng.normal(size=(12,)).astype(np.float32)
    scores = 1 / (1 + np.exp(-(x.astype(np.float64) @ router)))
    weights, experts = route(
        jnp.asarray(x), jnp.asarray(router), 3, scoring="sigmoid",
        select_bias=jnp.asarray(bias))
    chosen = np.argsort(-(scores + bias), axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(experts, chosen)
    picked = np.take_along_axis(scores, chosen, axis=-1)
    # float32 sigmoids against float64: a few ulps of values under one
    np.testing.assert_allclose(
        weights, picked / picked.sum(-1, keepdims=True), atol=1e-6)
    plain = np.argsort(-scores, axis=-1, kind="stable")[:, :3]
    assert (np.sort(plain, -1) != np.sort(chosen, -1)).any()
    # without a bias: the top scores themselves, not renormalised on demand
    weights, experts = route(
        jnp.asarray(x), jnp.asarray(router), 3, norm_topk=False,
        scoring="sigmoid")
    np.testing.assert_array_equal(experts, plain)
    np.testing.assert_allclose(
        weights, np.take_along_axis(scores, plain, axis=-1), atol=1e-6)
    # ties go to the lower expert
    _, tied = route(jnp.zeros((2, 16)), jnp.asarray(router), 3,
                    scoring="sigmoid")
    np.testing.assert_array_equal(tied, [[0, 1, 2]] * 2)


def test_moe_ffn_passes_the_scoring_through_with_an_eighth_held():
    """One expert of eight held, sigmoid routing with a bias: ``moe_ffn``'s
    part is the loop over the held expert with the sigmoid weights, and the
    counts are over all eight."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    bias = jnp.asarray(0.5 * rng.normal(size=(8,)), jnp.float32)
    experts = {
        "w_gate": jnp.asarray(0.3 * rng.normal(size=(1, 16, 8)), jnp.float32),
        "w_up": jnp.asarray(0.3 * rng.normal(size=(1, 16, 8)), jnp.float32),
        "w_down": jnp.asarray(0.3 * rng.normal(size=(1, 8, 16)), jnp.float32),
    }
    with jax.default_matmul_precision("highest"):
        got, counts = moe_ffn(
            x, router, experts, top_k=2, experts_held=(5, 6),
            scoring="sigmoid", select_bias=bias)
        weights, chosen = route(x, router, 2, scoring="sigmoid",
                                select_bias=bias)
        hidden = jax.nn.silu(x @ experts["w_gate"][0]) * (
            x @ experts["w_up"][0])
        want = (jnp.sum(jnp.where(chosen == 5, weights, 0.0), axis=-1)[:, None]
                * (hidden @ experts["w_down"][0]))
    assert int(counts.sum()) == 24 * 2 and 0 < int(counts[5]) < 24
    np.testing.assert_allclose(got, want, atol=1e-5 * np.abs(want).max())
