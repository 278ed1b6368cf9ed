"""The sorted-runs scatter-add compiled for a TPU v5e that is described,
not attached (the TPU's compiler is installed here): what Pallas'
interpreter cannot see (tiling, memory spaces, what Mosaic refuses) and
what only the partitioner shows (a row-sharded table takes no
collective). Nothing runs; no time comes from this.

The topology is described inside a fixture, never at import, and every
test of this kind lives in this one file: only one process at a time
may load the TPU's library, and a worker keeps it until it exits.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu.models.wordembedding import device_train
from multiverso_tpu.sharding import mesh as meshlib
from multiverso_tpu.updater import UpdateEngine, rules

ROWS, COLS = 1_000_004, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("chips", [1, 4])
def test_the_rows_program_compiles_for_a_v5e(topo, chips):
    mesh = Mesh(np.array(topo.devices[:chips]), (meshlib.SHARD_AXIS,))
    rows = NamedSharding(mesh, P(meshlib.SHARD_AXIS, None))
    everywhere = NamedSharding(mesh, P())
    engine = UpdateEngine(None, (ROWS, COLS), np.float32, 1, rows)
    k = (2, rules.FAST_MIN_IDS)
    assert rules.fast_rows((ROWS, COLS), np.float32, 2 * k[1], mesh)
    shaped = jax.ShapeDtypeStruct
    compiled = engine._rows.lower(
        shaped((ROWS, COLS), jnp.float32, sharding=rows), None,
        shaped(k, jnp.int32, sharding=everywhere),
        shaped(k + (50,), jnp.float32, sharding=everywhere),
        np.zeros(4, np.float32), np.int32(0)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute"):
        assert collective not in text
    memory = compiled.memory_analysis()
    # the table is updated in place (its tiles pad the rows to eights)
    assert memory.alias_size_in_bytes >= ROWS * COLS * 4 // chips
    assert memory.temp_size_in_bytes < 50e6


@pytest.mark.parametrize("whole", [False, True])
def test_adam_s_rows_form_compiles_at_a_row_of_4096_columns(topo, whole,
                                                            monkeypatch):
    """An embedding at hidden 4,096 (solar250b.ps-8k): the lazy rows form
    keeps Adam's formula out of the writes of a row that wide, because the
    TPU's compiler refuses the write fused with it (16.12 MB of scoped
    vector memory, of 16): the second case pins that refusal, so that a
    compiler that takes it fused shows here."""
    if whole:
        monkeypatch.setattr(rules, "WIDE_ROW_BYTES", 1 << 30)
    rows, cols, k = 2048, 4096, (2, 8192)
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    engine = UpdateEngine(rules.AdamRule(), (rows, cols), np.float32, 1)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    table = shaped((rows, cols), jnp.float32)
    lowered = engine._rows.lower(
        table, (table, table, shaped((), jnp.int32)), shaped(k, jnp.int32),
        shaped(k + (cols,), jnp.float32), np.zeros(4, np.float32),
        np.int32(0))
    if whole:
        with pytest.raises(Exception, match="vmem"):
            lowered.compile()
    else:
        assert "scatter" in lowered.compile().as_text()


# The local trainer's group program (device_train._group_fn) and the
# model-average one (_ma_group_fn) end every step in the same
# scatter-add, with no mesh: the platform rules.fast_rows asks for is
# the default backend's, the CPU's here, so the test steers it. The
# functions behind the lru_caches are called, so that no program traced
# this way is left where a CPU test would find it.
C, W, K, NEG_BLOCK, GROUP = 32768, 5, 5, 8, 8
STREAM = 1_000_000


def _group_args(table, stream, whole, keys, n_kept):
    """The arguments of a group program as shapes: tables, the kept
    stream and its sentences, the alias tables, key(s), the group's
    bases and learning rates, the kept count(s)."""
    shaped = jax.ShapeDtypeStruct
    return (table, table, stream, stream,
            shaped((ROWS,), jnp.float32, sharding=whole),
            shaped((ROWS,), jnp.int32, sharding=whole), keys,
            shaped((GROUP,), jnp.int32, sharding=whole),
            shaped((GROUP,), jnp.float32, sharding=whole), n_kept)


@pytest.mark.parametrize("chips", [1, 4])
def test_the_trainers_group_program_compiles_for_a_v5e(
        topo, monkeypatch, chips):
    monkeypatch.setattr(rules, "_platform", lambda mesh: "tpu")
    assert rules.fast_rows((ROWS, COLS), np.float32, C)
    mesh = Mesh(np.array(topo.devices[:chips]), (meshlib.SHARD_AXIS,))
    whole = NamedSharding(mesh, P())
    split = NamedSharding(mesh, P(meshlib.SHARD_AXIS))
    shaped = jax.ShapeDtypeStruct
    table = shaped((ROWS, COLS), jnp.float32, sharding=whole)
    if chips == 1:
        group = device_train._group_fn.__wrapped__(
            C, W, K, False, NEG_BLOCK)
        args = _group_args(
            table, shaped((STREAM,), jnp.int32, sharding=whole),
            whole, shaped((2,), jnp.uint32, sharding=whole),
            shaped((), jnp.int32, sharding=whole))
    else:  # every chip a replica and a quarter of the stream
        group = device_train._ma_group_fn.__wrapped__(
            mesh, C, W, K, NEG_BLOCK)
        args = _group_args(
            table,
            shaped((chips * STREAM,), jnp.int32, sharding=split), whole,
            shaped((chips, 2), jnp.uint32, sharding=split),
            shaped((chips,), jnp.int32, sharding=split))
    compiled = group.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    # Both tables are updated in place through the scan and the
    # kernel's loop: a copy of one would be a table's size (512 MB) of
    # temporaries. What is there is what the program had with XLA's
    # scatter (121.6 MB then, 122.1 with the kernel, 123.4 with the
    # listed ends): the padded stream and its sentences, a step's
    # gathered rows and gradients, the concatenated deltas, the sorted
    # ids' lists and one chunk of sorted delta rows.
    assert memory.alias_size_in_bytes >= 2 * ROWS * COLS * 4
    assert memory.temp_size_in_bytes < 150e6


# -- a language model's tables and kernels (multiverso_tpu/models/lm) ----------
# The embedding of benchmark/configs/smallthinker-21ba3b-l4.json: 37,984
# rows of 2560 floats, twenty lane tiles a row, under Adam with the
# step's 16,384 token ids as device keys.
LM_ROWS, LM_COLS, LM_IDS = 37_984, 2560, (2, 8192)


def test_the_kernel_is_refused_a_row_wider_than_one_tile(topo, monkeypatch):
    """Why ``rules.fast_rows`` sends a wide table to XLA's scatter: on a
    table more than 128 lanes wide the TPU's compiler refuses the
    kernel's one-row DMAs."""
    from multiverso_tpu.updater import row_scatter
    monkeypatch.setattr(rules, "_platform", lambda mesh: "tpu")
    assert not rules.fast_rows((LM_ROWS, LM_COLS), np.float32, 16384)
    assert not rules.fast_rows((LM_ROWS, 256), np.float32, 16384)
    assert rules.fast_rows((LM_ROWS, 128), np.float32, 16384)
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    shaped = jax.ShapeDtypeStruct
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(row_scatter.scatter_add).lower(
            shaped((LM_ROWS, 256), jnp.float32, sharding=one),
            shaped((16384,), jnp.int32, sharding=one),
            shaped((16384, 256), jnp.float32, sharding=one)).compile()


def test_adams_rows_program_compiles_for_a_v5e_at_the_embeddings_width(
        topo, monkeypatch):
    monkeypatch.setattr(rules, "_platform", lambda mesh: "tpu")
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    engine = UpdateEngine(rules.create_rule("adam"), (LM_ROWS, LM_COLS),
                          np.float32, 1)
    shaped = jax.ShapeDtypeStruct
    table = shaped((LM_ROWS, LM_COLS), jnp.float32, sharding=one)
    compiled = engine._rows.lower(
        table, (table, table, shaped((), jnp.int32, sharding=one)),
        shaped(LM_IDS, jnp.int32, sharding=one),
        shaped(LM_IDS + (LM_COLS,), jnp.float32, sharding=one),
        np.zeros(4, np.float32), np.int32(0)).compile()
    memory = compiled.memory_analysis()
    # the table and both moments are updated in place
    assert memory.alias_size_in_bytes >= 3 * LM_ROWS * LM_COLS * 4
    # the summed deltas and the three tables' gathered and stepped rows
    assert memory.temp_size_in_bytes < 8 * 16384 * LM_COLS * 4


def _splash_kernel(sizes, t, per_group, mask, qk_lanes=128, v_lanes=128):
    """``model._splash`` at the sizes its rule chooses for the call
    (``"chosen"``), or the same kernel at 512 everywhere (``"plain"``, what
    every call ran until PR 62 and what a kind not in the rule's table
    still runs)."""
    from multiverso_tpu.models.lm import model as lm
    if sizes == "chosen":
        return lm._splash(t, per_group, mask, qk_lanes, v_lanes)
    return lm._splash_at(t, per_group, mask,
                         lm._blocks_within(lm.PLAIN_BLOCKS, t))


SIZES = pytest.mark.parametrize("sizes", ["plain", "chosen"])


@SIZES
@pytest.mark.parametrize("window", [0, 4096])
def test_the_attention_kernel_compiles_for_a_v5e_at_8192_positions(
        topo, window, sizes):
    """Splash attention forward and backward for one sequence of the
    published head counts (4 key-value heads, 7 query heads each)."""
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    shaped = jax.ShapeDtypeStruct
    t = 8192

    def loss(q, k, v):
        out = jax.vmap(_splash_kernel(sizes, t, 7, window))(q, k, v)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        shaped((4, 7, t, 128), jnp.bfloat16, sharding=one),
        shaped((4, t, 128), jnp.bfloat16, sharding=one),
        shaped((4, t, 128), jnp.bfloat16, sharding=one)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # no [heads, T, T] array: 28 x 8192 x 8192 x 4 B would be 7.5 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 400e6


def test_the_grouped_products_compile_for_a_v5e_at_the_experts_widths(
        topo, monkeypatch):
    """``grouped_mm`` forward and backward through the Pallas kernel a TPU
    takes: 16 experts of 2560 x 768 and of 768 x 2560 over a sequence's
    49,152 assignment rows, float32 weight gradients."""
    from multiverso_tpu.models.lm import model as lm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    shaped = jax.ShapeDtypeStruct

    def loss(x, sink, w, sizes):
        return jnp.sum(lm.grouped_mm(x, w, sink, sizes))

    for k, n in ((2560, 768), (768, 2560)):
        assert lm._use_gmm(49152, k, n)
        compiled = jax.jit(jax.grad(loss, (0, 1))).lower(
            shaped((49152, k), jnp.bfloat16, sharding=one),
            shaped((16, k, n), jnp.float32, sharding=one),
            shaped((16, k, n), jnp.bfloat16, sharding=one),
            shaped((16,), jnp.int32, sharding=one)).compile()
        # the rows' gradient and the weights' gradient
        assert compiled.as_text().count("tpu_custom_call") >= 2
        assert compiled.memory_analysis().temp_size_in_bytes < 700e6


# -- block diffusion (benchmark/configs/sdar-30b-a3b-l6.json) --------------------

@SIZES
def test_the_attention_kernel_compiles_under_the_block_diffusion_mask(
        topo, sizes):
    """Splash attention forward and backward under the program's own mask
    object (``lm.Mask.blockdiff``: integer division and comparisons on
    the positions' indices inside the kernel) for one sequence's 2 x 4096
    positions at the published head counts (4 key-value heads, 8 query
    heads each)."""
    from multiverso_tpu.models.lm import model as lm
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    shaped = jax.ShapeDtypeStruct
    t, mask = 8192, lm.Mask.blockdiff(4096, 4)

    def loss(q, k, v):
        out = jax.vmap(_splash_kernel(sizes, t, 8, mask))(q, k, v)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        shaped((4, 8, t, 128), jnp.bfloat16, sharding=one),
        shaped((4, t, 128), jnp.bfloat16, sharding=one),
        shaped((4, t, 128), jnp.bfloat16, sharding=one)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    # no [heads, T, T] array: 32 x 8192 x 8192 x 4 B would be 8.6 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 400e6


def test_the_grouped_products_compile_at_the_block_diffusion_widths(
        topo, monkeypatch):
    """16 experts of 2048 x 768 and of 768 x 2048 over the 65,536
    assignment rows of a sequence's two copies (8 a position)."""
    from multiverso_tpu.models.lm import model as lm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    shaped = jax.ShapeDtypeStruct

    def loss(x, sink, w, sizes):
        return jnp.sum(lm.grouped_mm(x, w, sink, sizes))

    for k, n in ((2048, 768), (768, 2048)):
        assert lm._use_gmm(65536, k, n)
        compiled = jax.jit(jax.grad(loss, (0, 1))).lower(
            shaped((65536, k), jnp.bfloat16, sharding=one),
            shaped((16, k, n), jnp.float32, sharding=one),
            shaped((16, k, n), jnp.bfloat16, sharding=one),
            shaped((16,), jnp.int32, sharding=one)).compile()
        assert compiled.as_text().count("tpu_custom_call") >= 2
        assert compiled.memory_analysis().temp_size_in_bytes < 900e6


# -- the stream mixers (benchmark/configs/xing4-29b-a4b-l5.json) -----------------

def test_the_mixers_passes_compile_for_a_v5e_at_the_published_widths(
        topo, monkeypatch):
    """One sublayer of four streams of 3584 over 4096 tokens, forward and
    pulled, on sequence 1 of a step's two: each pass over the streams is a
    Pallas kernel (streams_kernels.py: stats, write, weighted, sums, dx,
    Sinkhorn's two), the sequence is read where it lies in the stack and
    the results are written into the stacks the program returns, so
    beside those two stacks the program holds no array as large as a
    sequence's streams but the feed-forward's input."""
    from multiverso_tpu.models.lm import model as lm, streams
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    shaped = jax.ShapeDtypeStruct
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "xing4-29b-a4b-l5.json")) as f:
        cfg = lm.LMConfig.from_dict(json.load(f))
    n, c, t = cfg.hc_mult, cfg.hidden, 4096
    assert (n, c, cfg.hc_iters) == (4, 3584, 20)
    k = 2 * n + n * n

    def f_vjp(u):
        v, pull = jax.vjp(jnp.tanh, u)
        return v, None, lambda dv: (pull(dv)[0], ())

    def sublayer(hc, xs, dys):
        b = jnp.int32(1)
        y, _, pull = streams.sublayer_vjp(
            cfg, hc, streams.Of(xs, b), f_vjp, into=streams.Of(xs, b))
        dx, d_hc, _ = pull(streams.Of(dys, b))
        return y, d_hc, pull(dx)[0]

    hc = {"phi": shaped((k, n * c), jnp.float32, sharding=one),
          "b": shaped((k,), jnp.float32, sharding=one),
          "a": shaped((3,), jnp.float32, sharding=one)}
    stack = shaped((2, n * c, t), jnp.float32, sharding=one)
    compiled = jax.jit(sublayer, donate_argnums=(1,)).lower(
        hc, stack, stack).compile()
    text = compiled.as_text()
    for kernel in ("mv_hc_stats", "mv_hc_write", "mv_hc_weighted",
                   "mv_hc_sums", "mv_hc_dx", "mv_hc_sinkhorn",
                   "mv_hc_sinkhorn_pull"):
        assert kernel in text, kernel
    # no copy of a sequence's streams (235 MB) or of a stack
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


# -- the fourth family's attention (benchmark/configs/laguna-xs2-33b-a3b-l5.json) --

@SIZES
@pytest.mark.parametrize("per_group,window", [(6, 0), (8, 512)])
def test_the_attention_kernel_compiles_at_both_of_laguna_s_kinds(
        topo, per_group, window, sizes):
    """Splash attention forward and backward for one sequence of 8192 at
    the published head counts of each kind of layer (8 key-value heads: 6
    query heads each under the causal mask, 8 each under a window of 512,
    which is no wider than the kernel's block)."""
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    shaped = jax.ShapeDtypeStruct
    t = 8192

    def loss(q, k, v):
        out = jax.vmap(_splash_kernel(sizes, t, per_group, window))(q, k, v)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        shaped((8, per_group, t, 128), jnp.bfloat16, sharding=one),
        shaped((8, t, 128), jnp.bfloat16, sharding=one),
        shaped((8, t, 128), jnp.bfloat16, sharding=one)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # no [heads, T, T] array: 64 x 8192 x 8192 x 4 B would be 17 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 800e6


# -- latent attention's kernel (models/lm/latent.py): q and k wider than v, or
# all three of two lane tiles; each head a group of its own ----------------------

@pytest.mark.parametrize("heads,qk,v,tokens", [
    (4, 192, 128, 4096),        # xing4-29b-a4b-l5: 4 of 32 heads held
    (32, 192, 128, 8192),       # kimi-linear-48b-a3b-l5's latent layer
    (20, 256, 256, 8192)])      # glm47-flash-30b-a3b-l5: every head
@SIZES
def test_latent_attention_s_kernel_compiles_at_the_published_lanes(
        topo, heads, qk, v, tokens, sizes):
    """Splash attention forward and backward for one sequence as
    ``latent.core`` calls it: q [heads, 1, T, nope + rope], k [heads, T,
    nope + rope], v [heads, T, v_head_dim], no lane padded."""
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    shaped = jax.ShapeDtypeStruct

    def loss(q, k, v):
        out = jax.vmap(_splash_kernel(sizes, tokens, 1, 0, q.shape[-1],
                                      v.shape[-1]))(q, k, v)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        shaped((heads, 1, tokens, qk), jnp.bfloat16, sharding=one),
        shaped((heads, tokens, qk), jnp.bfloat16, sharding=one),
        shaped((heads, tokens, v), jnp.bfloat16, sharding=one)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # no [heads, T, T] array (20 x 8192 x 8192 x 4 B would be 5.4 GB): the
    # output and the three gradients' float32 beside their bfloat16
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 16 * heads * tokens * (qk + v)


# -- heads under a tile (lfm2-8b-a1b-l8: 8 groups of 4 query heads of 64
# lanes, PR 63) ------------------------------------------------------------------

@SIZES
def test_the_attention_kernel_compiles_at_heads_of_half_a_tile(topo, sizes):
    """The library's three kernels (forward, dkv, dq) take q, k and v of 64
    lanes as they are: no lane is padded at the kernel's door."""
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    shaped = jax.ShapeDtypeStruct
    t = 8192

    def loss(q, k, v):
        out = jax.vmap(_splash_kernel(sizes, t, 4, 0, 64, 64))(q, k, v)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        shaped((8, 4, t, 64), jnp.bfloat16, sharding=one),
        shaped((8, t, 64), jnp.bfloat16, sharding=one),
        shaped((8, t, 64), jnp.bfloat16, sharding=one)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    # no head padded to a tile: no bfloat16 array of 128 lanes (the float32
    # [.., 8192, 128] beside the kernels is the library's log-sum-exp)
    assert "bf16[8,4,8192,128]" not in text and "bf16[8,8192,128]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 500e6


def test_a_convolution_sublayer_s_chain_is_one_pass_each_way(topo):
    """models/lm/shortconv.py at the published widths, one sequence of 8192
    tokens, forward and pull: between the two products the compiler leaves
    fusions alone (no copy of a [8192, 2048] float32 array for the shifts
    along positions), and what the program holds is a few such arrays."""
    from multiverso_tpu.models.lm import model as lm, shortconv
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "lfm2-8b-a1b-l8.json")) as f:
        cfg = lm.LMConfig.from_dict(json.load(f))
    t, h = 8192, cfg.hidden
    assert (h, cfg.conv_taps) == (2048, 3)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def sublayer(mats, small, x, d):
        out, _, pull = shortconv.attention_vjp(
            cfg, mats, lm._zeros_like_f32(mats), small, x)
        return out, pull(d)

    shapes = cfg.layer_shapes(0)
    mats = {n: shaped(shapes[n], jnp.bfloat16) for n in shortconv.MATRICES}
    small = {n: shaped(shapes[n], jnp.float32)
             for n in ("norm_attn", shortconv.TAPS)}
    compiled = jax.jit(sublayer).lower(
        mats, small, shaped((t, h), jnp.float32),
        shaped((t, h), jnp.float32)).compile()
    top = [line for line in compiled.as_text().splitlines()
           if "shortconv.taps" in line and " copy(" in line]
    assert not top, top[:3]
    # h W_in [8192, 6144] float32 is 201 MB; a handful of its size, not dozens
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def _granite():
    from multiverso_tpu.models.lm import model as lm
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "granite-4.0-h-micro-l10.json")) as f:
        return lm.LMConfig.from_dict(json.load(f))


def _wide_copies(text, t, inner):
    """The program's copies of a [T, inner] float32 array, either way
    round."""
    return [line for line in text.splitlines() if " copy(" in line and (
        f"f32[{t},{inner}]" in line.split(" copy(")[0]
        or f"f32[{inner},{t}]" in line.split(" copy(")[0])]


def test_a_state_space_sublayer_compiles_at_the_published_widths(
        topo, monkeypatch):
    """models/lm/ssd.py at granite-4.0-h-micro's widths, one sequence of 8192
    tokens in chunks of 256, forward and pull, the scan as
    models/lm/ssd_kernels.py's two kernels (the backend patched to say
    ``tpu``): what the program holds is a few arrays of the projection's
    size ([8192, 8512] float32 is 279 MB), and neither a run's within-chunk
    factor ([8, 64, 256, 256] float32, 134 MB) nor a [8192, 64, 64] array
    nor a turning copy of X, Y or their cotangents (the kernels read them
    turned, as the convolution leaves them)."""
    from multiverso_tpu.models.lm import model as lm, ssd
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    cfg = _granite()
    t, h = 8192, cfg.hidden
    assert (h, cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_state) == (
        2048, 64, 64, 128) and ssd.chunk_of(cfg, t) == 256
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssd.scan_counter(cfg, t) == "LM_SSD_SCAN_KERNEL"

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def sublayer(mats, small, x, d):
        out, counts, pull = ssd.attention_vjp(
            cfg, mats, lm._zeros_like_f32(mats), small, x)
        return out, counts, pull(d)

    shapes = cfg.layer_shapes(0)
    mats = {n: shaped(shapes[n], jnp.bfloat16) for n in ssd.MATRICES}
    small = {n: shaped(shapes[n], jnp.float32)
             for n in ("norm_attn",) + ssd.SMALL}
    compiled = jax.jit(sublayer).lower(
        mats, small, shaped((t, h), jnp.float32),
        shaped((t, h), jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "mv_ssd_scan_fwd" in text and "mv_ssd_scan_bwd" in text
    assert "f32[8,64,256,256]" not in text and "[8192,64,64]" not in text
    assert not _wide_copies(text, t, 4096)
    # 1.40e9 (the plain scan's program: 1.28e9, and 2.2e9 was its bound)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6e9


@pytest.mark.parametrize("pulled", [False, True])
def test_the_state_space_scan_is_two_kernels_at_the_cell_s_shapes(
        topo, pulled, monkeypatch):
    """``ssd.scan`` and its pull at 8,192 positions, 64 heads of 64 lanes, a
    state of 128, the arrays handed over turned as the layer program has
    them: one kernel forward, the keeping forward walk and the backward one
    with the pull, no [8, 64, 256, 256] array, and for temporaries the
    states kept (67 MB: the compiler reads 68.0) and the turned [T, 128]
    arrays, not one array of [8192, 4096] (134 MB)."""
    from multiverso_tpu.models.lm import ssd
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    t, heads, lanes, state = 8192, 64, 64, 128
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssd.scan_in_kernels(t, heads, lanes, state)

    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)

    def scan(x, dt, a_log, b, c):
        y, deep = ssd.scan(x.T.reshape(t, heads, lanes), dt.T, a_log, b.T,
                           c.T)
        return y.reshape(t, -1).T, deep

    def pull(x, dt, a_log, b, c, dy):
        return jax.vjp(lambda *a: scan(*a)[0], x, dt, a_log, b, c)[1](dy)

    args = (shaped(heads * lanes, t), shaped(heads, t), shaped(heads),
            shaped(state, t), shaped(state, t))
    compiled = jax.jit(pull).lower(*args, args[0]).compile() if pulled \
        else jax.jit(scan).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 + pulled
    assert "mv_ssd_scan_fwd" in text
    assert ("mv_ssd_scan_bwd" in text) is pulled
    assert "f32[8,64,256,256]" not in text
    assert not _wide_copies(text, t, heads * lanes)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < (100e6 if pulled else 16e6)


# -- the pass between the attention's projections and its kernel -----------------
# (models/lm/attn_kernels.py; interpreted against the chain in
# tests/test_lm_attn_pass.py)

@pytest.mark.parametrize("tokens,groups,per_group,lanes,norm", [
    (8192, 4, 7, 0, False),         # smallthinker, layer 0: no rotary
    (8192, 4, 7, 128, False),       # smallthinker
    (8192, 4, 8, 128, True),        # sdar: head norms, both copies
    (8192, 8, 6, 64, False),        # laguna, full attention: half the lanes
    (8192, 8, 8, 128, False),       # laguna, window
    (16384, 4, 8, 128, True)])      # keye
def test_the_attention_s_pass_compiles_at_the_published_head_counts(
        topo, tokens, groups, per_group, lanes, norm):
    """``heads_in`` and its pull as Mosaic takes them, one sequence: each
    a kernel, nothing beside it but the float32 the pull's bfloat16 is
    widened to for ``mm``'s rule (which rounds it back: the pair cancels
    inside a layer program), and no float32 array of the heads' size."""
    from multiverso_tpu.models.lm import attn_kernels
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    d = 128

    def shaped(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    how = attn_kernels.Pass(per_group, d, lanes, norm, 1e-6, d ** -0.5,
                            jnp.bfloat16)
    products = tuple(shaped(tokens, groups * n * d)
                     for n in (per_group, 1, 1))
    scales = (shaped(d), shaped(d)) if norm else ()
    tables = (shaped(tokens, lanes // 2),) * 2 if lanes else ()
    forward = jax.jit(lambda *a: attn_kernels.heads_in(how, *a)).lower(
        *products, scales, tables).compile()
    assert forward.as_text().count("tpu_custom_call") == 1
    assert forward.memory_analysis().temp_size_in_bytes < 1e6
    heads = tuple(shaped(*s, dtype=jnp.bfloat16) for s in (
        (groups, per_group, tokens, d), (groups, tokens, d),
        (groups, tokens, d)))

    def pull(qf, kf, vf, scales, tables, cotangents):
        return jax.vjp(lambda *a: attn_kernels.heads_in(how, *a, tables),
                       qf, kf, vf, scales)[1](cotangents)

    backward = jax.jit(pull).lower(*products, scales, tables,
                                   heads).compile()
    assert backward.as_text().count("tpu_custom_call") == 1
    # the three cotangents in bfloat16, before they are widened
    assert backward.memory_analysis().temp_size_in_bytes < 1.1 * 2 * (
        tokens * groups * (per_group + 2) * d)


@pytest.mark.parametrize("tokens,heads", [(8192, 20), (1024, 4)])
def test_the_latent_pass_compiles_at_glm_s_heads(topo, tokens, heads):
    """``latent_kernels.heads_in`` and its pull as Mosaic takes them at
    ``192 | 64 | 256`` lanes a head (``glm30b.ps-8k``: a ``kv`` head of 448
    lanes starts mid-tile every second time, so a grid step takes a pair):
    each a kernel, nothing beside it but the float32 that the pull's
    bfloat16 is widened to for ``mm``'s rule, and no array of the heads'
    size (interpreted against the chain in tests/test_lm_mla_pass.py)."""
    from multiverso_tpu.models.lm import latent_kernels
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def shaped(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    how = latent_kernels.Pass(192, 64, 256, 256 ** -0.5, jnp.bfloat16)
    assert latent_kernels.fits(tokens, heads, how) and how.together == 2
    products = (shaped(tokens, heads * 256),
                shaped(tokens, heads * 448, dtype=jnp.bfloat16),
                shaped(tokens, 64))
    tables = (shaped(tokens, 32),) * 2
    forward = jax.jit(lambda *a: latent_kernels.heads_in(how, *a)).lower(
        *products, tables).compile()
    assert forward.as_text().count("tpu_custom_call") == 1
    assert forward.memory_analysis().temp_size_in_bytes < 1e6
    laid = tuple(shaped(*s, dtype=jnp.bfloat16) for s in (
        (heads, 1, tokens, 256), (heads, tokens, 256), (heads, tokens, 256)))

    def pull(qf, kvf, k_r, tables, cotangents):
        return jax.vjp(lambda *a: latent_kernels.heads_in(how, *a, tables),
                       qf, kvf, k_r)[1](cotangents)

    backward = jax.jit(pull).lower(*products, tables, laid).compile()
    assert backward.as_text().count("tpu_custom_call") == 1
    # ``W_qb``'s product's cotangent in bfloat16, before it is widened
    assert backward.memory_analysis().temp_size_in_bytes < 1.1 * 2 * (
        tokens * heads * 256)


# -- the delta rule's scan ------------------------------------------------------------
# (models/lm/delta_kernels.py; interpreted against the plain scan and the
# recurrence in tests/test_lm_kda_kernels.py)

def test_the_delta_scan_s_kernels_compile_at_the_cell_s_shapes(topo):
    """Both kernels as Mosaic takes them at 8192 positions, 32 heads of 128
    lanes, chunks of 64 (``kimi48b.ps-8k``): the forward pass alone is one
    kernel; made again and pulled it is two (the forward that keeps a
    chunk's state, ``M`` and diagonal blocks a head, and the backward
    walk), and what lives between them is what was kept (537 MB: 268 of
    states, ``M``'s 64 lanes padded to 128) and the turns of [T, H, 128] to
    [T, H 128] that a program's own parameters need (a layer program's
    fusions write the kernels' layout themselves)."""
    from multiverso_tpu.models.lm import delta, delta_kernels
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    tokens, heads, lanes = 8192, 32, 128
    assert delta_kernels.shapes_fit(tokens, lanes, lanes, delta.CHUNK,
                                    delta.BLOCK)
    wide = jax.ShapeDtypeStruct((tokens, heads, lanes), jnp.float32,
                                sharding=one)
    beta = jax.ShapeDtypeStruct((tokens, heads), jnp.float32, sharding=one)

    def scan(*args):
        return delta_kernels.scan(*args, delta.DEEP)

    forward = jax.jit(scan).lower(wide, wide, wide, wide, beta).compile()
    assert forward.as_text().count("tpu_custom_call") == 1
    assert "mv_kda_scan_fwd" in forward.as_text()

    def pulled(q, k, v, g, beta, cotangent):
        return jax.vjp(lambda *a: scan(*a)[0], q, k, v, g, beta)[1](cotangent)

    backward = jax.jit(pulled).lower(wide, wide, wide, wide, beta,
                                     wide).compile()
    text = backward.as_text()
    assert text.count("tpu_custom_call") == 2 and "mv_kda_scan_bwd" in text
    kept = 4 * heads * (tokens // delta.CHUNK) * sum(
        rows * max(columns, lanes) for rows, columns in delta_kernels.KEPT)
    assert kept < backward.memory_analysis().temp_size_in_bytes \
        < kept + 6 * 4 * tokens * heads * lanes


def test_a_delta_sublayer_holds_its_heads_as_the_projections_leave_them(
        topo, monkeypatch):
    """``delta.attention_vjp`` and its pull at the cell's widths, as the
    layer programs hold them: three kernels (the forward scan, and in the
    pull the forward that keeps and the backward walk) and NO array in [T,
    H, 128]'s own tiles (8 heads by 128 lanes), which is a copy from and to
    the projections' [T, H 128] (8 positions by 128 lanes): ``gates`` and
    ``output`` are delta_passes.py's kernels on [T, H 128] (``heads_apart``'s
    view where they are not), and the parts hand each
    other [T, H 128]. (The parent held 16 such arrays a sublayer and the
    convolutions' fusions lost their scope to the turn: 258 ms a step under
    no scope, 68 after, PERF.md section 5.)"""
    from multiverso_tpu.models.lm import delta, model as lm
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "kimi-linear-48b-a3b-l5.json")) as f:
        cfg = lm.LMConfig.from_dict(json.load(f))
    tokens = 8192

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    shapes = delta.shapes(cfg)
    mats = {n: shaped(shapes[n], jnp.bfloat16) for n in delta.MATRICES}
    small = {n: shaped(s, jnp.float32) for n, s in shapes.items()
             if n not in delta.MATRICES}
    small["norm_attn"] = shaped((cfg.hidden,), jnp.float32)
    x = shaped((tokens, cfg.hidden), jnp.float32)

    def sublayer(mats, small, x, d):
        sinks = {n: jnp.zeros(m.shape, jnp.float32)
                 for n, m in mats.items()}
        out, deep, pull = delta.attention_vjp(cfg, mats, sinks, small, x)
        return out, deep, pull(d)

    text = jax.jit(sublayer).lower(mats, small, x, x).compile().as_text()
    # the scan's three, and since PR 64 the passes around it: the gates
    # twice, q's and k's convolutions in them (the forward's; made again
    # for the scan's transpose: their own pull keeps its inputs alone and
    # reads no third), their pull, the gated norm and its pull; since PR 69
    # v's convolution beside the gates (twice) and, where the convolutions
    # are differentiated, q's and k's (v's feeds nothing there and is
    # dropped) and the three pulls
    assert text.count("tpu_custom_call") == 15
    assert text.count("mv_kda_scan_bwd") >= 1
    for name in ("mv_kda_gates_pull", "mv_kda_out_pull", "mv_kda_conv",
                 "mv_kda_conv_pull"):
        assert name in text
    heads, d = cfg.kda_heads, cfg.kda_head_dim
    assert f"f32[{tokens},{heads * d}]" in text
    assert f"f32[{tokens},{heads},{d}]" not in text


# -- the delta layers' passes ------------------------------------------------------------
# (models/lm/delta_passes.py; interpreted against ``delta.gates`` and
# ``delta.output``'s gated norm in tests/test_lm_kda_passes.py)

#: held heads and beta's scale: ``kimi48b.ps-8k``, ``solar250b.ps-8k``
DELTA_CELLS = {"kimi": (32, 1.0), "solar": (8, 2.0)}


@pytest.mark.parametrize("cell", list(DELTA_CELLS))
@pytest.mark.parametrize("which, kernel, results_bf16", [
    ("gates", "mv_kda_gates", 0), ("conv_gates", "mv_kda_gates", 0),
    ("gates_pull", "mv_kda_gates_pull", 1),
    ("norm", "mv_kda_out", 1), ("norm_pull", "mv_kda_out_pull", 1),
    ("conv", "mv_kda_conv", 0), ("conv_pull", "mv_kda_conv_pull", 1)])
def test_a_delta_pass_compiles_at_a_cell_s_shapes(topo, cell, which, kernel,
                                                  results_bf16):
    """Each of the four passes (the gates' also with q's and k's
    convolutions of four weights in it: a roll along the rows, the tile
    before a block as a second view) and a short convolution alone with its
    pull (the tile AFTER a block as a third view) as Mosaic takes it at 8192
    tokens of 32 and of 8 heads of 128 lanes: ONE kernel, and no float32
    intermediate beside
    it: what the program holds besides its arguments and results is the
    bfloat16 array that a pass hands on widened (``mm`` rounds it back:
    the pair folds away in a layer program) and the small tensors' partial
    sums a block of tokens (a convolution's: one a weight)."""
    from multiverso_tpu.models.lm import delta_passes
    heads, scale = DELTA_CELLS[cell]
    tokens, lanes = 8192, heads * delta_passes.LANES
    assert delta_passes.fits(tokens, delta_passes.LANES)
    how = delta_passes.Pass(heads, scale, 1e-6)
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)

    wide, thin = shaped(tokens, lanes), shaped(tokens, heads)
    gates_in = (shaped(heads), shaped(lanes), wide, wide, wide, thin)
    norm_in = (shaped(delta_passes.LANES), wide, wide)

    def gates(*a):
        return delta_passes.gates(how, *a)

    def norm(*a):
        return delta_passes.gated_norm(how, *a)

    def conv(x, w):
        return delta_passes.conv(how, x, w)

    taps = shaped(lanes, 4)
    program, args = {
        "gates": (gates, gates_in),
        "conv_gates": (lambda *a: delta_passes.conv_gates(how, *a),
                       (taps, taps) + gates_in),
        "gates_pull": (lambda *a: jax.vjp(gates, *a[:6])[1](a[6:]),
                       gates_in + (wide, wide, wide, thin)),
        "norm": (norm, norm_in),
        "norm_pull": (lambda *a: jax.vjp(norm, *a[:3])[1](a[3]),
                      norm_in + (wide,)),
        "conv": (conv, (wide, taps)),
        "conv_pull": (lambda x, w, g: jax.vjp(conv, x, w)[1](g),
                      (wide, taps, wide))}[which]
    compiled = jax.jit(program).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and kernel in text
    sums = (4 if which == "conv_pull" else 2) * (
        tokens // delta_passes.TOKENS) * 8 * lanes * 4
    # beta's cotangent, 32 of 128 lanes wide, lies in whole tiles
    thin_bf16 = tokens * 128 * 2
    assert compiled.memory_analysis().temp_size_in_bytes <= (
        results_bf16 * tokens * lanes * 2 + thin_bf16 + sums + (1 << 20))


#: ``backward_program``'s temporaries for a sparse delta layer at the parent
#: of PR 69 (6e79fbd: PR 64's passes in it, the convolutions XLA's),
#: compiled for the same described v5e: bytes
PARENT_BACKWARD_TEMPORARIES = {
    "kimi-linear-48b-a3b-l5": 4_082_397_184,
    "solar-open2-250b-a15b-l4": 2_997_893_120}
#: One bfloat16 [8192, 2304] array: with q's and k's pulls custom calls the
#: compiler's memory-space assignment keeps one more such array in fast
#: memory through kimi's experts and evicts a third to HBM there (the live
#: set at the program's peak is the parent's and this copy). On the chip
#: ``peak_hbm_gb`` FELL (12.1780 -> 12.1725, PERF.md section 6, PR 69).
EVICTED = {"kimi-linear-48b-a3b-l5": 8192 * 2304 * 2 + (1 << 16)}


@pytest.mark.parametrize("config", list(PARENT_BACKWARD_TEMPORARIES))
def test_a_delta_layer_s_backward_program_is_no_larger_with_the_passes(
        topo, config, monkeypatch):
    """A sparse delta layer's backward program at the cell's sizes (two
    sequences of 8192), the passes and the convolutions' kernels in it: its
    temporaries are no more than they were with the convolutions XLA's
    (the rules keep their inputs alone, which ``attention_vjp`` holds
    anyway, no slope is kept, and no float32 intermediate lies between a
    pass and its consumer), but for ``EVICTED``."""
    from multiverso_tpu.models.lm import delta, model as lm, ps_train
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            f"{config}.json")) as f:
        cfg = lm.LMConfig.from_dict(json.load(f))
    tokens = 8192
    assert delta.passes_fused(cfg, tokens)
    kinds = cfg.layer_kinds()
    kind = next(k for k in kinds if k[2] and k[3] == "kda")
    shapes = cfg.layer_shapes(kinds.index(kind))

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    mats = {n: shaped(shapes[n], jnp.bfloat16)
            for n in cfg.matrices(kinds.index(kind))}
    small = {n: shaped(s) for n, s in shapes.items() if n not in mats}
    x = shaped((2, tokens, cfg.hidden))
    compiled = ps_train.backward_program(
        cfg, *kind[:2], tokens, kind[2], attention=kind[3]).lower(
        mats, small, x, x).compile()
    text = compiled.as_text()
    for name in ("mv_kda_gates", "mv_kda_gates_pull", "mv_kda_out",
                 "mv_kda_out_pull", "mv_kda_scan_bwd", "mv_kda_conv",
                 "mv_kda_conv_pull"):
        assert name in text
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= PARENT_BACKWARD_TEMPORARIES[config] + EVICTED.get(config, 0)
