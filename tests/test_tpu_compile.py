"""Kernels and main-path programs compile for the chip — without the chip.

The TPU's compiler is installed here and compiles for a chip that is
*described*, not attached (``/opt/skills/guides/on-chip-measurement`` §2).
Interpret-mode kernel tests and the eight virtual CPU devices cannot see
what it refuses: a slice off the tiling, too much fast memory, a program
that does not fit, a collective too many.  These compiles are that check,
kept as tests so every later PR is guarded at no chip time.  A compile that
passes is not a chip run.

All of it lives in THIS file on purpose: the topology is described inside a
module-scoped fixture (never at import, never ``autouse``, never in
``conftest.py``), only the xdist worker that is handed this file loads the
TPU library, and every compile runs in the test's own process.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to JAX's persistent cache
    but cannot be read back without the chip: the next one would warn and
    compile again.  Keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _fits(compiled) -> None:
    m = compiled.memory_analysis()
    total = (
        m.temp_size_in_bytes + m.argument_size_in_bytes
        + m.output_size_in_bytes
    )
    assert total < V5E_HBM_BYTES, f"program needs {total} bytes of HBM"


# (b, s, h, d): ViT-B/16 at 224² and ViT-L/16 at 384² (CLS token: a
# sequence no block divides), and the long-sequence shape the kernel was
# timed at
FLASH_SHAPES = [(8, 197, 12, 64), (8, 577, 16, 64), (4, 4096, 8, 128)]


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_flash_attention_kernel_compiles(
    shape, direction, one_chip, no_compile_cache
):
    """The Pallas kernel itself — ``interpret=False``, steered here because
    ``jax.default_backend()`` is the CPU — lowers through Mosaic for the
    chip, and the lowering contains the kernel, not the dense substitute."""
    from sparkdl_tpu.ops import flash_attention

    def forward(q, k, v):
        return flash_attention(q, k, v, interpret=False)

    def backward(q, k, v):
        return jax.grad(
            lambda *a: forward(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    spec = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    fn = forward if direction == "forward" else backward
    compiled = jax.jit(fn).lower(spec, spec, spec).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_inception_featurizer_program_compiles(one_chip, no_compile_cache):
    """The fused featurizer program as ``DeepImageFeaturizer`` really
    builds it — uint8 in, cast + resize + flip-folded stem + preprocess +
    InceptionV3 in bf16, batch buffer donated — at batch 64."""
    from sparkdl_tpu import DeepImageFeaturizer

    stage = DeepImageFeaturizer(
        inputCol="image", outputCol="features", modelName="InceptionV3",
        modelWeights="random",
    )
    forward, entry = stage._build_forward()
    h, w = entry.input_size
    batch = jax.ShapeDtypeStruct((64, h, w, 3), np.uint8, sharding=one_chip)
    compiled = (
        jax.jit(forward._fn, donate_argnums=(0,)).lower(batch).compile()
    )
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (64, 2048) and out.dtype == jnp.float32
    _fits(compiled)


def test_served_masked_block_program_compiles(one_chip, no_compile_cache):
    """The ONE executable of a ragged endpoint as the batcher builds it: the
    masked ``(n_slots, *item)`` block with the input prologue (uint8 ingest,
    resize from source size, Keras-parity normalise) fused in front of the
    model."""
    from sparkdl_tpu.models import get_keras_application_model
    from sparkdl_tpu.serving.batcher import MicroBatcher, ServingConfig
    from sparkdl_tpu.serving.cache import ProgramCache
    from sparkdl_tpu.transformers.utils import make_input_prologue
    from sparkdl_tpu.utils.benchlib import fill_variables

    entry = get_keras_application_model("MobileNetV2")
    h, w = entry.input_size
    module = entry.make_module(dtype=jnp.bfloat16)
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        variables = fill_variables(
            module, jnp.zeros((1, h, w, 3), jnp.float32)
        )

    def forward(x):
        out = module.apply(variables, x.astype(jnp.bfloat16))
        return out.astype(jnp.float32)

    n_slots, source = 32, (256, 320)
    endpoint = MicroBatcher(
        "mnv2", forward, ServingConfig(max_batch=n_slots), ProgramCache(),
        item_shape=(*source, 3), dtype=np.uint8, fingerprint="test:mnv2",
        prologue=make_input_prologue((h, w), entry.preprocess),
    )
    try:
        fused = endpoint._masked_fused()
    finally:
        endpoint.close()
    block = jax.ShapeDtypeStruct(
        (n_slots, *source, 3), np.uint8, sharding=one_chip
    )
    mask = jax.ShapeDtypeStruct((n_slots,), np.bool_, sharding=one_chip)
    compiled = jax.jit(fused, donate_argnums=(0, 1)).lower(block, mask).compile()
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape[0] == n_slots and out.dtype == jnp.float32
    _fits(compiled)


@pytest.mark.parametrize("weighted", [False, True], ids=["pmean", "psum"])
def test_dp_train_step_allreduces_gradients_once(
    weighted, topo, no_compile_cache
):
    """The data-parallel Keras train step on a four-chip mesh: the compiled
    program all-reduces every gradient exactly once — not twice (an
    explicit reduce on top of the transpose's own), not never — in both
    the plain-mean and the weighted branch."""
    import keras

    from sparkdl_tpu.estimators.losses import (
        get_optimizer,
        get_per_sample_loss_fn,
        mean_squared_error,
    )
    from sparkdl_tpu.parallel.keras_train import (
        init_keras_train_state,
        make_keras_train_step,
    )

    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = keras.Sequential([
            keras.layers.Input((16, 16, 3)),
            keras.layers.Conv2D(5, 3),  # the only f32[3,3,3,5] in the step
            keras.layers.BatchNormalization(),
            keras.layers.ReLU(),
            keras.layers.GlobalAveragePooling2D(),
            keras.layers.Dense(7, activation="softmax"),
        ])
    tx = get_optimizer("sgd", 0.1)
    # a callable has no per-sample form: the estimator then takes the
    # unweighted pmean branch
    loss = (
        get_per_sample_loss_fn("mean_squared_error") if weighted
        else mean_squared_error
    )
    mesh = Mesh(np.array(topo.devices), ("data",))
    step = make_keras_train_step(model, loss, tx, mesh, weighted=weighted)
    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P("data"))
    state = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=replicated
        ),
        jax.eval_shape(lambda: init_keras_train_state(model, tx)),
    )
    batch = {
        "x": jax.ShapeDtypeStruct((32, 16, 16, 3), np.float32, sharding=sharded),
        "y": jax.ShapeDtypeStruct((32, 7), np.float32, sharding=sharded),
    }
    if weighted:
        batch["w"] = jax.ShapeDtypeStruct((32,), np.float32, sharding=sharded)
    text = step.lower(state, batch).compile().as_text()
    reduced = re.findall(r"= ([^\n]*?) all-reduce(?:-start)?\(", text)
    assert reduced, "no all-reduce at all in the data-parallel step"
    kernel_reduces = sum(shape.count("f32[3,3,3,5]") for shape in reduced)
    assert kernel_reduces == 1, (
        f"the conv kernel's gradient is all-reduced {kernel_reduces} times:"
        f" {reduced}"
    )


# -- SDAR-MoE at its published widths (PR 31) --------------------------------

SDAR_WIDTHS = dict(
    vocab_size=151936, hidden_size=2048, num_attention_heads=32,
    num_key_value_heads=4, head_dim=128, num_experts=128,
    num_experts_per_tok=8, moe_intermediate_size=768,
)


def _sdar_shapes(cfg, one_chip):
    from sparkdl_tpu.models import sdar_moe

    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip),
        sdar_moe.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """``ops.moe.grouped_dot`` asks the backend which product to use, and
    here that is the CPU: steer it in the test, as the guide says."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("rows", [8192, 65536, 24],
                         ids=["block", "prefill", "odd"])
def test_grouped_expert_product_compiles(
        rows, one_chip, no_compile_cache, as_on_the_chip):
    """The grouped matmul at the block step's and the prefill's row counts
    (and one that no tile divides), over a stack of 6 x 128 experts."""
    from sparkdl_tpu.ops.moe import grouped_dot

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for k, n in ((2048, 768), (768, 2048)):
        compiled = jax.jit(grouped_dot).lower(
            spec((rows, k)), spec((768, k, n)), spec((768,), jnp.int32)
        ).compile()
        assert "tpu_custom_call" in compiled.as_text()
        _fits(compiled)


def _entry_ops(text):
    """(result shape, opcode, op_name) of the entry computation's own
    instructions: what is a pass of its own over memory."""
    entry = text[text.index("\nENTRY "):]
    ops = []
    for line in entry.splitlines():
        head = re.match(r"\s*(?:ROOT )?%\S+ = (\S+?)\{\S* ([\w-]+)\(", line)
        if head:
            name = re.search(r'op_name="([^"]*)"', line)
            ops.append((*head.groups(), name.group(1) if name else ""))
    return ops


@pytest.mark.parametrize("tokens, width, experts, held, top_k, temp_limit", [
    (2048, 4096, 72, (0, 36), 10, 0.35e9),
    (1024, 2048, 128, None, 8, 0.03e9),
], ids=["granite-prefill", "sdar-block"])
def test_expert_layer_moves_each_pairs_row_once(
        tokens, width, experts, held, top_k, temp_limit, one_chip,
        no_compile_cache, as_on_the_chip):
    """``moe_ffn`` alone over a stack of two layers, at granite's prefill
    dispatch (k = 10 is no multiple of a tile's 8 rows, half of the experts
    held elsewhere) and at SDAR's block step: around the three grouped
    products the pairs' rows [n * k, d] are written by the forward gather and
    by the last product and by nothing else; no pass fills the rows of
    out-of-bounds indices behind a gather; the k parts of a token are never
    laid out as [n, k, d] nor widened to float32 in memory (PERF.md
    section 6, PR 36: 437.6 MB of temporaries before, 270.8 MB now)."""
    from sparkdl_tpu.ops.moe import moe_ffn

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n_held = experts if held is None else held[1] - held[0]
    stack = {
        "w_gate": spec((2, n_held, width, 768)),
        "w_up": spec((2, n_held, width, 768)),
        "w_down": spec((2, n_held, 768, width)),
    }
    compiled = jax.jit(
        lambda x, router, stack, index: moe_ffn(
            x, router, stack, top_k=top_k, experts_held=held,
            stack_index=index)
    ).lower(spec((tokens, width)), spec((width, experts)), stack,
            spec((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    pairs = tokens * top_k
    assert f"[{tokens},{top_k},{width}]" not in text
    assert f"f32[{pairs},{width}]" not in text
    rows = [(opcode, name) for shape, opcode, name in _entry_ops(text)
            if shape == f"bf16[{pairs},{width}]"]
    assert sorted(opcode for opcode, _ in rows) == ["custom-call", "fusion"]
    assert all(name.endswith("/gather") for opcode, name in rows
               if opcode == "fusion"), rows
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit


@pytest.fixture(scope="module")
def sdar_block_programs():
    """The block step's compiled shapes, kept for the module: the chained
    case compares itself with the plain one."""
    return {}


def _sdar_block_program(kept, chained, one_chip):
    """The cell's block step (256 rows, 640 slots, 4 forwards) at the
    published widths, two layers deep — the layers are scanned, so depth
    changes the arguments' bytes and not the program."""
    from sparkdl_tpu.models import sdar_moe

    if chained in kept:
        return kept[chained]
    cfg = sdar_moe.SdarMoeConfig(num_hidden_layers=2, **SDAR_WIDTHS)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = spec((2, 256, 4, 640, 128), jnp.bfloat16)
    kept[chained] = jax.jit(
        lambda p, ck, cv, prefix, start, where, tokens, known, pending:
        sdar_moe.block_step(p, cfg, ck, cv, prefix, start, where, tokens,
                            known, pending, steps=4, mask_id=151669),
        donate_argnums=(1, 2),
    ).lower(
        _sdar_shapes(cfg, one_chip), cache, cache, spec((256,)),
        spec((256,)), spec((2,)), spec((256, 4)), spec((256, 4), jnp.bool_),
        spec((256, 4)) if chained else None,
    ).compile()
    return kept[chained]


@pytest.mark.parametrize("chained", [False, True], ids=["plain", "chained"])
def test_sdar_block_step_compiles_and_fits(
        chained, sdar_block_programs, one_chip, no_compile_cache,
        as_on_the_chip):
    """Both shapes of the cell's block step: a batch's first block, and
    every later one, whose first forward carries the block before it as well
    (2,048 tokens) and writes it into the cache.  The bytes of the six-layer
    cell are reckoned from the two-layer compile."""
    compiled = _sdar_block_program(sdar_block_programs, chained, one_chip)
    text = compiled.as_text()
    # a scan over the layers (its carry is the residual stream [rows,
    # positions, 2048]) with its three grouped products exists once a SHAPE
    # of forward, inside the loop over the denoising steps, and not once a
    # step: the plain forward's, and a chained step's first, which carries
    # both blocks' positions.  No scan for a commit.
    scans = re.findall(
        r"= \(s32\[\]\S* bf16\[256,([48]),2048\]\S* [^\n]* while\(", text)
    assert sorted(scans) == ["4", "8"][:1 + int(chained)]
    assert text.count("tpu_custom_call") == 3 * len(scans)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * 2 * 256 * 4 * 640 * 128 * 2  # donated
    layer = 2 * 623_087_872 + 2 * 256 * 4 * 640 * 128 * 2 * 2  # weights, cache
    six = m.argument_size_in_bytes + 4 * layer + m.temp_size_in_bytes
    assert 0.25 * V5E_HBM_BYTES < six < 0.85 * V5E_HBM_BYTES, six
    if chained:  # the wider forward's room
        plain = _sdar_block_program(sdar_block_programs, False, one_chip)
        assert (m.temp_size_in_bytes
                - plain.memory_analysis().temp_size_in_bytes) <= 0.3e9


# -- Granite 4.0-H at its published widths (PR 35) ----------------------------

GRANITE_WIDTHS = dict(
    vocab_size=50176, hidden_size=4096, num_attention_heads=32,
    num_key_value_heads=8, num_local_experts=36, routed_experts=72,
    experts_held=(0, 36), num_experts_per_tok=10, intermediate_size=768,
    shared_intermediate_size=1536, mamba_n_heads=128, mamba_d_head=64,
    mamba_d_state=128, attention_multiplier=0.0078125,
    embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=16,
)


def _granite(one_chip, rows=64, span=2176):
    """The cell's config three layers deep (Mamba, attention, Mamba: both
    scan bodies' shapes and the attention layer; depth changes the
    arguments' bytes, hardly the program), its params and state as shapes on
    the described chip, and what the seven layers left out would add."""
    from sparkdl_tpu.models import granite_hybrid as gh

    cfg = gh.GraniteHybridConfig(
        num_hidden_layers=3, layer_types=("mamba", "attention", "mamba"),
        **GRANITE_WIDTHS)

    def on_chip(spec):
        return jax.ShapeDtypeStruct(spec.shape, spec.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(
        lambda path, s: jax.ShapeDtypeStruct(
            s, jnp.float32 if path[-1].key in ("dt_bias", "a_log", "d")
            else jnp.bfloat16, sharding=one_chip),
        gh.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    state = jax.tree_util.tree_map(
        on_chip, gh.state_spec(cfg, rows, span, jnp.bfloat16))
    mamba_layer = 2 * 461_242_368 + rows * (4 * 1_048_576 + 2 * 3 * 8448)
    return gh, cfg, params, state, 7 * mamba_layer


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_granite_programs_compile_and_fit(
        program, one_chip, no_compile_cache, as_on_the_chip):
    """The cell's two programs: a prefill segment of 16 pairs x 128
    positions against the state of 64 rows, and 8 decode steps.  The bytes
    of the ten-layer cell are reckoned from the three-layer compile."""
    gh, cfg, params, state, left_out = _granite(one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if program == "prefill":
        compiled = jax.jit(
            lambda p, s, *rest: gh.prefill(p, cfg, s, *rest),
            donate_argnums=(1,),
        ).lower(params, state, ints(16, 128), ints(16), ints(16), ints(16)
                ).compile()
    else:
        compiled = jax.jit(
            lambda p, s: gh.decode(p, cfg, s, 8), donate_argnums=(1,)
        ).lower(params, state).compile()
    text = compiled.as_text()
    # the grouped expert product is the Pallas kernel: gate, up and down of
    # each of the two scan bodies and of the attention layer
    assert text.count("tpu_custom_call") == 9
    if program == "prefill":
        # a segment scores its row's cache by blocks, out of the leaf where
        # it lies (PR 38): no float32 scores over the span, no copy of the
        # 16 rows' cache
        assert not re.findall(r"f32\[[\d,]*,2176\]", text)
        assert "bf16[16,8,2176,128]" not in text
    m = compiled.memory_analysis()
    recurrent = 2 * 64 * (4 * 1_048_576 + 2 * 3 * 8448)
    cache = 2 * 64 * 8 * 2176 * 128 * 2
    assert m.alias_size_in_bytes >= recurrent + cache  # the state is donated
    ten = m.argument_size_in_bytes + left_out + m.temp_size_in_bytes
    assert 0.25 * V5E_HBM_BYTES < ten < 0.92 * V5E_HBM_BYTES, ten
    # no copy of the whole recurrent state beside the donated one: a layer
    # reads and writes its own rows only
    # (prefill: 754,259,968 bytes and a tenth; a float32 copy of the expert
    # layer's pairs [tokens * k, d] alone would add 336 MB)
    assert m.temp_size_in_bytes < (0.83e9 if program == "prefill" else 1.0e9)


# -- Solar-Open2 at its published widths (PR 37) -------------------------------

def test_chunked_delta_rule_kernel_compiles(one_chip, no_compile_cache):
    """``ops/delta_rule``'s kernel alone at the cell's prefill shape: 16
    pairs x 128 positions, 64 heads of 128 channels, chunks of 64 in
    sub-blocks of 16, bfloat16 operands beside the float32 decays and
    state."""
    from sparkdl_tpu.ops import delta_rule

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wide = (16, 128, 64, 128)
    compiled = jax.jit(
        lambda *args: delta_rule._kda_chunked_kernel(*args, 64)
    ).lower(
        spec(jnp.bfloat16, *wide), spec(jnp.bfloat16, *wide),
        spec(jnp.bfloat16, *wide), spec(jnp.float32, *wide),
        spec(jnp.float32, 16, 128, 64), spec(jnp.float32, 16, 64, 128, 128),
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    _fits(compiled)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_solar_programs_compile_and_fit(
        program, one_chip, no_compile_cache, as_on_the_chip):
    """The cell's two programs, whole (the three KDA layers are one scan
    body, so the four layers compile as two): a prefill segment of 16 pairs
    x 128 positions against the state of 128 rows, and 8 decode steps."""
    import json
    import os

    from sparkdl_tpu.models import solar_open2 as so

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            here, "chipbench", "configs",
            "solar_open2_250b-generate.json")) as fh:
        cfg = so.SolarOpen2Config.from_dict(json.load(fh))
    rows, span = 128, 4224

    def on_chip(spec):
        return jax.ShapeDtypeStruct(spec.shape, spec.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map_with_path(
        lambda path, s: jax.ShapeDtypeStruct(
            s, jnp.float32 if path[-1].key in so.FLOAT32_LEAVES
            else jnp.bfloat16, sharding=one_chip),
        so.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    state = jax.tree_util.tree_map(
        on_chip, so.state_spec(cfg, rows, span, jnp.bfloat16))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if program == "prefill":
        compiled = jax.jit(
            lambda p, s, *rest: so.prefill(p, cfg, s, *rest),
            donate_argnums=(1,),
        ).lower(params, state, ints(16, 128), ints(16), ints(16), ints(16)
                ).compile()
    else:
        compiled = jax.jit(
            lambda p, s: so.decode(p, cfg, s, 8), donate_argnums=(1,)
        ).lower(params, state).compile()
    # the grouped expert product is the Pallas kernel: gate, up and down of
    # the attention layer and of the KDA layers' scan body; a prefill's scan
    # body holds the chunked rule's kernel besides, once (PR 40)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == (7 if program == "prefill" else 6)
    if program == "prefill":
        assert len(re.findall(r"%kda_chunked[.\d]* = ", text)) == 1
        # none of the chunked form's tensors a (row, head, chunk, sub-block)
        # is left in HBM: 67 MB each in plain XLA
        assert not re.findall(r"f32\[16,64,2,4,16,", text)
        # as in the granite prefill (PR 38): [8, 8, 128, 4224] float32 a row
        # and 2 x 138 MB of gathered cache a dispatch before
        assert not re.findall(r"f32\[[\d,]*,4224\]", text)
        assert "bf16[16,8,4224,128]" not in text
    m = compiled.memory_analysis()
    # 3,308 M parameters: 6.62 GB of bfloat16
    weights = sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(params))
    assert 6.61e9 < weights < 6.63e9
    recurrent = 3 * rows * (4 * 1_048_576 + 2 * 3 * 3 * 8192)
    cache = 2 * rows * 8 * span * 128 * 2
    assert m.alias_size_in_bytes >= recurrent + cache  # the state is donated
    whole = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert 0.25 * V5E_HBM_BYTES < whole < 0.85 * V5E_HBM_BYTES, whole
    # no second copy of the whole KDA state (1.6 GB) among the temporaries
    # of a prefill segment; a decode step holds one layer's decayed state
    # and its successor (0.54 GB each) beside the 128 rows' scores
    assert m.temp_size_in_bytes < (1.25e9 if program == "prefill" else 2.6e9)


def test_mla_token_kernel_compiles(one_chip, no_compile_cache):
    """``ops/mla``'s decode kernel alone at the cell's decode shape: 128
    rows' absorbed queries of 16 heads x 576 channels and their new latents
    against the channel-major view of the latent leaf, 5 layers x 7,424
    slots, blocks of 512 (the span is 14.5 of them: the last one moved
    back); the leaf aliased to the kernel's second output, which writes
    each row's new slot back in place."""
    from sparkdl_tpu.ops import mla

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda query, new, latent, layer, position: mla._token_attention(
            query, new, latent, layer, position, 192 ** -0.5,
            mla.TOKEN_BLOCK),
        donate_argnums=(2,),
    ).lower(
        spec(jnp.bfloat16, 128, 16, 576), spec(jnp.bfloat16, 128, 576),
        spec(jnp.bfloat16, 5, 128, 7424, 576), spec(jnp.int32),
        spec(jnp.int32, 128),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    _fits(compiled)
    # the leaf is read and written where it lies: donated into the kernel's
    # output, never copied, nothing of its size among the temporaries
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 5 * 128 * 7424 * 576 * 2
    assert not [line for line in text.splitlines()
                if ("bf16[5,128,7424,576]" in line
                    or "bf16[5,128,576,7424]" in line)
                and re.search(r"= \S+ copy(-start)?\(", line)]
    assert m.temp_size_in_bytes < 1e8


@pytest.mark.parametrize("heads,rows,span,masked", [
    (64, 16, 30848, True), (16, 128, 7424, False)],
    ids=["glm5", "moonlight"])
def test_mla_segment_kernel_compiles(heads, rows, span, masked, one_chip,
                                     no_compile_cache, as_on_the_chip):
    """``ops/mla``'s prefill kernel alone at a cell's prefill shape: 16
    pairs of 128 queries' absorbed queries x ``heads`` heads x 576 channels
    against the channel-major view of the latent leaf (5 layers x ``rows``
    rows x ``span`` slots), blocks of 512, and GLM-5's selection mask; the
    heads in groups of 32 (GLM-5: two a pair) or all 16 (Moonlight)."""
    from sparkdl_tpu.ops import mla

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shape = mla.Shape(heads=heads, nope=192, rope=64, v=256, rank=512,
                      theta=1e6, kv_eps=1e-6, scale=256 ** -0.5)
    group = mla.segment_group(shape, 128, span)
    assert group == min(heads, 32)
    chosen = spec(jnp.bfloat16, 16, 128, span) if masked else None
    compiled = jax.jit(
        lambda query, latent, layer, rows, start, chosen:
        mla._segment_attention(query, latent, layer, rows, start,
                               shape.scale, shape.rank, 512, group, chosen)
    ).lower(
        spec(jnp.bfloat16, 16, heads, 128, 576),
        spec(jnp.bfloat16, 5, rows, span, 576), spec(jnp.int32),
        spec(jnp.int32, 16), spec(jnp.int32, 16), chosen,
    ).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%mla_segment[.\d]* = ", text)) == 1
    assert text.count("tpu_custom_call") == 1
    _fits(compiled)
    # the leaf is read where it lies: its channel-major view is a bitcast,
    # and there is no copy of it, nor anything of a layer's share of it among
    # the temporaries (a standalone compile's only ones: the queries and the
    # output in the kernel's layout)
    assert re.search(rf"bf16\[5,{rows},576,{span}\]\S* bitcast\(", text)
    assert not [line for line in text.splitlines()
                if f"bf16[5,{rows},{span},576]" in line
                and re.search(r"= \S+ copy(-start)?\(", line)]
    queries = 16 * heads * 128 * 576 * 2
    assert compiled.memory_analysis().temp_size_in_bytes <= 2.5 * queries


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_moonlight_programs_take_the_latent_leaf_where_it_lies(
        program, one_chip, no_compile_cache, as_on_the_chip):
    """The cell's two programs, whole (every MLA layer of a kind in one
    scan): a prefill segment of 16 pairs x 128 positions and 8 decode
    steps, against the latent cache of 128 rows at a span of
    7,424.  The chip lays the leaf out span-minor (576 channels would pad
    to 640); a scatter of a slot's channels made XLA copy the whole 5.47 GB
    leaf into the other layout and back, and a slice of the decode's latent
    output a layer's cache (0.97 GB): neither is left."""
    import json
    import os

    from sparkdl_tpu.models import moonlight as ml

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            here, "chipbench", "configs",
            "moonlight_16b_a3b-generate.json")) as fh:
        cfg = ml.MoonlightConfig.from_dict(json.load(fh))
    rows, span = 128, 7424
    params = jax.tree_util.tree_map_with_path(
        lambda path, s: jax.ShapeDtypeStruct(
            s, jnp.float32 if path[-1].key in ml.FLOAT32_LEAVES
            else jnp.bfloat16, sharding=one_chip),
        ml.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        ml.state_spec(cfg, rows, span, jnp.bfloat16))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if program == "prefill":
        compiled = jax.jit(
            lambda p, s, *rest: ml.prefill(p, cfg, s, *rest),
            donate_argnums=(1,),
        ).lower(params, state, ints(16, 128), ints(16), ints(16), ints(16)
                ).compile()
    else:
        compiled = jax.jit(
            lambda p, s: ml.decode(p, cfg, s, 8), donate_argnums=(1,)
        ).lower(params, state).compile()
    text = compiled.as_text()
    # gate, up and down of the expert layers' scan body; the attention's
    # kernel besides, once in each of the two layer scans' bodies (the dense
    # layer's and the expert layers')
    assert text.count("tpu_custom_call") == 5
    kernel = "mla_segment" if program == "prefill" else "mla_token"
    assert len(re.findall(rf"%{kernel}[.\d]* = ", text)) == 2
    if program == "decode":
        # the scores of every row against the whole span are gone from HBM
        assert "f32[128,7424,16]" not in text
        # the kernel writes each row's new slot itself: no 128-slot window
        # of every row gathered, and no scatter or dynamic-update-slice of
        # the leaf outside it
        assert "bf16[128,576,128]" not in text
        assert not [line for line in text.splitlines()
                    if re.search(r"bf16\[5,128,(7424,576|576,7424)\]", line)
                    and re.search(r"= \S+ (scatter|dynamic-update-slice)\(",
                                  line)]
    leaf = "bf16[5,128,7424,576]"
    # the kernel takes the channel-major view, a bitcast of the leaf
    assert not [line for line in text.splitlines()
                if (leaf in line or "bf16[5,128,576,7424]" in line)
                and re.search(r"= \S+ copy(-start)?\(", line)]
    assert f"{leaf}{{3,2,1,0" not in text  # the leaf in one layout only
    assert "bf16[128,7424,512]" not in text
    m = compiled.memory_analysis()
    # 3,093.5 M parameters: 6.19 GB of bfloat16; the latent cache 5.47 GB
    weights = sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(params))
    assert 6.18e9 < weights < 6.19e9
    latent = 5 * rows * span * 576 * 2
    assert m.alias_size_in_bytes >= latent  # the state is donated
    assert m.argument_size_in_bytes < weights + latent + 1e7
    # no second copy of the leaf, nor of a layer's share of it (1.09 GB)
    assert m.temp_size_in_bytes < 0.3e9


def test_dsa_select_kernel_compiles(one_chip, no_compile_cache):
    """``ops/dsa``'s selection kernel alone at the GLM-5 cell's prefill
    shape: 16 pairs of 128 queries x 32 indexer heads of 128 against the
    slot-major index leaf of 5 layers x 16 rows x 30,848 slots, keeping 2,048
    a query; the row's keys and the scores stay in fast memory."""
    from sparkdl_tpu.ops import dsa

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, w, keys, layer, rows, start: dsa._select(
            q, w, keys, layer, rows, start, 2048, dsa.SELECT_BLOCK)
    ).lower(
        spec(jnp.bfloat16, 16, 128, 32, 128), spec(jnp.float32, 16, 128, 32),
        spec(jnp.bfloat16, 5, 16, 30848, 128), spec(jnp.int32),
        spec(jnp.int32, 16), spec(jnp.int32, 16),
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    _fits(compiled)
    # the leaf is read where it lies: nothing of its size (632 MB) among the
    # temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 1e8


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_glm5_programs_compile_and_fit(
        program, one_chip, no_compile_cache, as_on_the_chip):
    """The GLM-5 cell's two programs whole: a prefill dispatch of 16 pairs x
    128 positions (consecutive segments of one row may share it) and 8
    decode steps, against the caches of 16 rows at a span of 30,848.  Both
    leaves are written and read where they lie: the latent (1.78 GB) by
    segments of whole write blocks that go back in one scatter of windows
    that do not overlap (written one pair after another, the leaf was copied
    into another layout and back, 4.1 GB of temporaries), the index keys
    (632 MB) by one scatter of whole slots (by windows as the latent, they
    were copied whole into another layout in every layer)."""
    import json
    import os

    from sparkdl_tpu.models import glm5

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            here, "chipbench", "configs",
            "glm_5_744b_a40b-generate.json")) as fh:
        cfg = glm5.Glm5Config.from_dict(json.load(fh))
    rows, span = 16, 30848
    params = jax.tree_util.tree_map_with_path(
        lambda path, s: jax.ShapeDtypeStruct(
            s, jnp.float32 if path[-1].key in glm5.FLOAT32_LEAVES
            else jnp.bfloat16, sharding=one_chip),
        glm5.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    state = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        glm5.state_spec(cfg, rows, span, jnp.bfloat16))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if program == "prefill":
        compiled = jax.jit(
            lambda p, s, *rest: glm5.prefill(p, cfg, s, *rest),
            donate_argnums=(1,),
        ).lower(params, state, ints(16, 128), ints(16), ints(16), ints(16)
                ).compile()
    else:
        compiled = jax.jit(
            lambda p, s: glm5.decode(p, cfg, s, 8), donate_argnums=(1,)
        ).lower(params, state).compile()
    text = compiled.as_text()
    # gate, up and down of the expert layers' scan body; in prefill the
    # selection kernel and the attention's besides, once each in each of the
    # two layer scans
    assert text.count("tpu_custom_call") == (7 if program == "prefill" else 3)
    assert len(re.findall(r"%dsa_select[.\d]* = ", text)) == (
        2 if program == "prefill" else 0)
    assert len(re.findall(r"%mla_segment[.\d]* = ", text)) == (
        2 if program == "prefill" else 0)
    # the prefill fold's float32 accumulator is held in fast memory
    assert "f32[64,128,576]" not in text
    assert not [line for line in text.splitlines()
                if ("bf16[5,16,30848,576]" in line
                    or "bf16[5,16,576,30848]" in line
                    or "bf16[5,16,30848,128]" in line)
                and re.search(r"= \S+ copy(-start)?\(", line)]
    m = compiled.memory_analysis()
    # 3,909.6 M parameters: 7.82 GB of bfloat16; the caches 3.47 GB
    weights = sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(params))
    assert 7.81e9 < weights < 7.83e9
    caches = rows * span * 5 * (576 + 128) * 2
    assert m.alias_size_in_bytes >= caches  # the state is donated
    assert m.argument_size_in_bytes < weights + caches + 1e7
    assert m.temp_size_in_bytes < (0.6e9 if program == "prefill" else 1e9)
