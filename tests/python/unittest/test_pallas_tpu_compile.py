"""The flash kernels, the routed experts' grouped products, the state-space
scan's kernels, the gated delta rule's, the convolution's backward and the
gated group norm's compiled for a
described (not attached) TPU v5e, at the benchmark cells' shapes and the
shape guards' corners: what interpret mode
cannot show — a slice Mosaic cannot tile, a transpose it cannot lower, more
VMEM than a kernel may use — fails here, on the CPU harness, at no chip time.
Nothing runs: results and times come from ``tools/tpu_numerics_check.py``
and the benchmark.

All such compiles live in this one file, and the topology is described in a
fixture (never at import): one process at a time may hold the TPU library,
and under xdist only the worker given this file loads it."""
import os
import re

import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import kda, ssm
from mxnet_tpu.ops.pallas_kernels import (flash_attention, flash_available,
                                          flash_blocks, grouped_available,
                                          grouped_matmul, grouped_matmul_t,
                                          kda_blocks, ssd_blocks)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, shape, dtype, causal=True, **blocks):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal, None, **blocks)
        return (out.astype(jnp.float32) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    text = compiled.as_text()
    # forward, dQ and dK/dV are there as Mosaic kernels
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    return text


# (B, H, T, D): opt-1.3b-steps' attention, then the corners T*D = 2**20 of
# flash_available at each lane width (tools/tpu_numerics_check.py runs them)
CELL = (4, 32, 2048, 64)
CORNERS = [(1, 1, 16384, 64), (1, 1, 8192, 128), (1, 1, 4096, 256)]


@pytest.mark.parametrize("causal", [True, False])
def test_the_benchmark_cells_shape_compiles_with_the_choosers_blocks(
        one_chip, causal):
    assert flash_blocks(2048, 64, 2) == (512, 512)
    text = _compile(one_chip, CELL, jnp.bfloat16, causal)
    # the results the benchmark's readers tell the kernels apart by
    assert "(bf16[128,2048,64]" in text and "f32[128,2048,1]" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("shape", CORNERS)
def test_the_guards_corners_compile(one_chip, shape, dtype):
    """The guard plans VMEM at the f32 upper bound, so what it admits has to
    compile with f32 operands too."""
    assert flash_available(shape)
    _compile(one_chip, shape, dtype)


@pytest.mark.parametrize("d", [8, 32, 96, 192])
def test_head_sizes_off_the_lane_width_compile(one_chip, d):
    """D is the sublane dimension of forward's and dQ's accumulators and is
    padded to whole 128-row tiles for their one transpose."""
    assert flash_available((1, 2, 1024, d))
    _compile(one_chip, (1, 2, 1024, d), jnp.bfloat16)


@pytest.mark.parametrize("heads,dtype", [(32, jnp.bfloat16),
                                         (2, jnp.float32)])
def test_value_heads_narrower_than_the_key_heads_compile(one_chip, heads,
                                                         dtype):
    """kimi-linear-steps-t4096's latent attention, (B, H, T) = (1, 32, 4096)
    with query/key heads of 192 and value heads of 128, bfloat16: all three
    kernels take both widths, and the results keep the value's.  (In
    float32 the same widths compile at 2 heads; at 32 the forward's scoped
    VMEM reads 16.07 MB of 16: as D = 256 in float32 does from 2 heads on,
    PERF.md 7.)"""
    q = jax.ShapeDtypeStruct((1, heads, 4096, 192), dtype, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, heads, 4096, 128), dtype, sharding=one_chip)
    assert flash_available(q.shape, q.shape, v.shape)

    def loss(q, k, v):
        return (flash_attention(q, k, v, True).astype(jnp.float32) ** 2).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, v).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in ("mxtpu_flash_fwd", "mxtpu_flash_dq", "mxtpu_flash_dkv"):
        assert name in text
    kind = "bf16" if dtype == jnp.bfloat16 else "f32"
    assert kind + "[%d,4096,128]" % heads in text \
        and kind + "[%d,4096,192]" % heads in text
    assert not re.search(r"\[[0-9,]*4096,4096\]", text)


@pytest.mark.parametrize("bq,bk", [(128, 128), (256, 512), (512, 256)])
def test_explicit_blocks_compile(one_chip, bq, bk):
    _compile(one_chip, (1, 4, 2048, 64), jnp.bfloat16, block_q=bq,
             block_k=bk)


# nemotron-twotower-steps-t4096's routed experts: 8 held, hidden 2688, width
# 1856 (14.5 x 128 lanes), the 8,192 rows set aside in blocks of 256.  The
# seven products of a layer's forward and backward, by operand shapes:
# (rows' width, matrix or second rows' shape, keywords)
ROWS, HELD, HIDDEN, WIDTH = 8192, 8, 2688, 1856
GROUPED = {
    "up": (HIDDEN, (HELD, WIDTH, HIDDEN), dict(
        transpose_rhs=True, act=lambda x: jnp.square(jnp.maximum(x, 0)))),
    "up_again": (HIDDEN, (HELD, WIDTH, HIDDEN), dict(
        transpose_rhs=True, out_dtype=jnp.float32)),
    "down": (WIDTH, (HELD, HIDDEN, WIDTH), dict(transpose_rhs=True)),
    "d_hid": (HIDDEN, (HELD, HIDDEN, WIDTH), dict(out_dtype=jnp.float32)),
    "d_rows": (WIDTH, (HELD, WIDTH, HIDDEN), {}),
    "d_up": (WIDTH, (ROWS, HIDDEN), None),
    "d_down": (HIDDEN, (ROWS, WIDTH), None)}


@pytest.mark.parametrize("product", sorted(GROUPED))
def test_the_routed_experts_products_compile_at_the_cells_shape(one_chip,
                                                                product):
    """The grid's extent is a traced count of blocks; a matrix is taken
    whole; a block is worked whole or as its lower 128 rows; the transposed
    products' tiles of 640 and 896 lanes hang over 1856's edge or cut 2688
    in three."""
    assert grouped_available(256, HIDDEN, WIDTH, 2)
    width, second, kw = GROUPED[product]
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    if kw is None:
        fn = lambda a, b, *t: grouped_matmul_t(a, b, *t, HELD)  # noqa: E731
    else:
        fn = lambda a, b, *t: grouped_matmul(a, b, *t, **kw)  # noqa: E731
    blocks = shaped((ROWS // 256,), jnp.int32)      # their experts, fills
    text = jax.jit(fn).lower(
        shaped((ROWS, width), jnp.bfloat16), shaped(second, jnp.bfloat16),
        blocks, blocks, shaped((), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert ("mxtpu_tgmm" if kw is None else "mxtpu_gmm") in text


# kimi-linear-steps-t4096's gated experts: 8 held, hidden 2304, width 1024,
# the 4,096 + 2,048 rows set aside in blocks of 256.  The products the gate
# adds to a layer's forward and backward (the others are the shapes above
# at these widths)
KIMI_ROWS, KIMI_HIDDEN, KIMI_WIDTH = 6144, 2304, 1024
GATED = {
    "gate": (KIMI_HIDDEN, (HELD, KIMI_WIDTH, KIMI_HIDDEN), dict(
        transpose_rhs=True, act=jax.nn.silu, out_dtype=jnp.float32)),
    "up": (KIMI_HIDDEN, (HELD, KIMI_WIDTH, KIMI_HIDDEN), dict(
        transpose_rhs=True, out_dtype=jnp.float32)),
    "down": (KIMI_WIDTH, (HELD, KIMI_HIDDEN, KIMI_WIDTH), dict(
        transpose_rhs=True)),
    "d_rows": (KIMI_WIDTH, (HELD, KIMI_WIDTH, KIMI_HIDDEN), {}),
    "d_gate": (KIMI_WIDTH, (KIMI_ROWS, KIMI_HIDDEN), None)}


@pytest.mark.parametrize("product", sorted(GATED))
def test_the_gated_experts_products_compile_at_the_cells_shape(one_chip,
                                                               product):
    from mxnet_tpu.ops.moe import capacity
    assert capacity(4096, 8, 256, 8)[:2] == (256, KIMI_ROWS)
    assert grouped_available(256, KIMI_HIDDEN, KIMI_WIDTH, 2)
    width, second, kw = GATED[product]
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    if kw is None:
        fn = lambda a, b, *t: grouped_matmul_t(a, b, *t, HELD)  # noqa: E731
    else:
        fn = lambda a, b, *t: grouped_matmul(a, b, *t, **kw)  # noqa: E731
    blocks = shaped((KIMI_ROWS // 256,), jnp.int32)
    text = jax.jit(fn).lower(
        shaped((KIMI_ROWS, width), jnp.bfloat16),
        shaped(second, jnp.bfloat16), blocks, blocks,
        shaped((), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


# nemotron-twotower-steps-t4096's state-space mixer, (B, T, H, P, G, N, chunk),
# then the corners of ``ssd_blocks``: a head a tile; four heads a tile; one
# group of 64 heads in steps of 8, chunks of 256; a state of 256
SSD_CELL = (1, 4096, 64, 64, 8, 128, 128)
SSD_CORNERS = [(2, 1024, 8, 128, 2, 128, 128), (1, 1024, 16, 32, 2, 128, 128),
               (1, 1024, 64, 64, 1, 128, 256), (1, 512, 16, 64, 2, 256, 128)]


def _compile_scan(one_chip, shape, dtype):
    bsz, t, h, p, g, n, chunk = shape
    assert ssd_blocks(t, h, p, g, n, chunk, jnp.dtype(dtype).itemsize)
    shaped = lambda dims, kind: jax.ShapeDtypeStruct(  # noqa: E731
        dims, kind, sharding=one_chip)
    leaf = shaped((h,), jnp.float32)

    def loss(*args):
        y = ssm._scan_kernels(*args, h, p, g, chunk)
        return (y.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        shaped((bsz, t, h * p + 2 * g * n), dtype), shaped((bsz, t, h), dtype),
        leaf, leaf, leaf).compile().as_text()
    # forward, the states formed again, backward: nothing run twice
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in ("mxtpu_ssd_fwd", "mxtpu_ssd_states", "mxtpu_ssd_bwd"):
        assert name in text
    # none of the (L, L) blocks, which the plain form keeps as float32
    # arrays of (..., chunk, chunk), is an array of the program
    assert not re.search(r"f32\[[0-9,]*\b%d,%d\]" % (chunk, chunk), text)
    return text


def test_the_scan_compiles_at_the_cells_shape(one_chip):
    """x, B and C are column blocks of the unsplit array: the kernels'
    operands are the op's own (1, 4096, 6144) input, three times, and the
    one large temporary is the states' (T / L, H P, N) float32 array."""
    assert ssd_blocks(*SSD_CELL[1:], 2) == 8
    text = _compile_scan(one_chip, SSD_CELL, jnp.bfloat16)
    assert "f32[1,32,4096,128]" in text
    assert not re.search(r"bf16\[[0-9,]*\b8,[0-9,]*\b128,8,64\]", text)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("shape", SSD_CORNERS)
def test_the_scans_corners_compile(one_chip, shape, dtype):
    _compile_scan(one_chip, shape, dtype)


# kimi-linear-steps-t4096's linear-attention mixer, (B, T, H, d_k, d_v,
# chunk), then the corners of ``kda_blocks``: 2 heads in chunks of 128 (eight
# sub-blocks to join); 32 heads with keys of 256 in chunks of 16 (nothing to
# join); 32 heads with values of 256, two sequences, chunks of 32
KDA_CELL = (1, 4096, 32, 128, 128, 64)
KDA_CORNERS = [(1, 256, 2, 128, 128, 128), (1, 64, 32, 256, 128, 16),
               (2, 128, 32, 128, 256, 32)]


def _compile_rule(one_chip, shape, dtype):
    bsz, t, h, dk, dv, chunk = shape
    assert kda_blocks(t, h, dk, dv, chunk, jnp.dtype(dtype).itemsize)
    shaped = lambda dims, kind: jax.ShapeDtypeStruct(  # noqa: E731
        dims, kind, sharding=one_chip)
    keys, vals = shaped((bsz, t, h * dk), dtype), shaped((bsz, t, h * dv),
                                                         dtype)

    def loss(*args):
        o = kda._scan_kernels(*args, h, chunk)
        return (o.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=tuple(range(7)))).lower(
        keys, keys, vals, keys, shaped((bsz, t, h), dtype),
        shaped((h,), jnp.float32), shaped((h * dk,), jnp.float32)
    ).compile().as_text()
    # forward, the states formed again, backward: nothing run twice
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in ("mxtpu_kda_fwd", "mxtpu_kda_states", "mxtpu_kda_bwd"):
        assert name in text
    # the solve is products inside the kernels, and none of the (L, L)
    # blocks, which the plain form keeps as float32 arrays of all chunks
    # and heads, (..., H, T / L, L, L) and the like, is an array of the
    # program: the one (L, L) array a chunk is the states pass's inverse,
    # (B, T / L, H L, L) (the states themselves, (B, T / L, H, d_v, d_k),
    # look like one where d_v = d_k = L)
    assert "triangular" not in text.lower()
    blocks = set(re.findall(r"f32\[[0-9,]*\b%d,%d\]" % (chunk, chunk), text))
    assert blocks <= {
        "f32[%d,%d,%d,%d]" % (bsz, t // chunk, h * chunk, chunk),
        "f32[%d,%d,%d,%d,%d]" % (bsz, t // chunk, h, dv, dk)}, blocks
    return text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_delta_rule_compiles_at_the_cells_shape(one_chip, dtype):
    """With the chooser's eight heads a step; the large temporaries are
    the states' (T / L, H, d_v, d_k) float32 array and the chunks' inverses,
    and no (T, d_k, d_v) state or (T / L, L, L, d) block exists."""
    assert kda_blocks(*KDA_CELL[1:], jnp.dtype(dtype).itemsize) == 8
    text = _compile_rule(one_chip, KDA_CELL, dtype)
    assert "f32[1,64,32,128,128]" in text and "f32[1,64,1024,128]" in text
    assert not re.search(r"f32\[[0-9,]*\b64,[0-9,]*\b16,16,128\]", text)
    assert "f32[1,4096,32,128,128]" not in text


def test_the_delta_rules_forward_alone_keeps_nothing_a_chunk(one_chip):
    """The forward is one kernel, and no array of the program has a chunk
    axis: no state a chunk, no (L, L) block a chunk."""
    bsz, t, h, dk, dv, chunk = KDA_CELL
    shaped = lambda dims, kind: jax.ShapeDtypeStruct(  # noqa: E731
        dims, kind, sharding=one_chip)
    keys = shaped((bsz, t, h * dk), jnp.bfloat16)
    text = jax.jit(lambda *a: kda._scan_kernels(*a, h, chunk)).lower(
        keys, keys, keys, keys, shaped((bsz, t, h), jnp.bfloat16),
        shaped((h,), jnp.float32), shaped((h * dk,), jnp.float32)
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "mxtpu_kda_fwd" in text and "triangular" not in text.lower()
    assert not re.search(r"\[%d,%d,[0-9,]+\]" % (bsz, t // chunk), text)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("shape", KDA_CORNERS)
def test_the_delta_rules_corners_compile(one_chip, shape, dtype):
    _compile_rule(one_chip, shape, dtype)


# the short causal convolutions of both hybrid cells, (B, T, C, a bias), then
# a corner: float32, 3 taps, row blocks of 16
CONV_CELLS = [(1, 4096, 6144, True), (1, 4096, 4096, False)]


@pytest.mark.parametrize("shape,dtype,k", [(s, jnp.bfloat16, 4)
                                           for s in CONV_CELLS]
                         + [((2, 48, 384, True), jnp.float32, 3)])
def test_the_convolutions_backward_compiles_at_the_cells_shapes(
        one_chip, monkeypatch, shape, dtype, k):
    """``causal_conv1d``'s gradient is one kernel, ``mxtpu_conv_bwd``, and
    the program keeps no float32 (B, T, C) array: what it holds beyond its
    inputs and outputs is under one such array's bytes.  The op takes the
    kernel on a TPU: the backend is steered here, in the test."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bsz, t, c, with_bias = shape
    shaped = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)

    def loss(x, w, b):
        y = ssm.causal_conv(x, w, b, jax.nn.silu)
        return (y.astype(jnp.float32) ** 2).sum()
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2) if with_bias
                                else (0, 1))).lower(
        shaped(bsz, t, c), shaped(c, k), shaped(c) if with_bias else None
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "mxtpu_conv_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < bsz * t * c * 4


# the gated group norm of nemotron-twotower-steps-t4096's Mamba mixers, (B, T,
# C, G), then corners: one group of 128 lanes; one group of 4,096 in float32,
# where the VMEM budget cuts the rows
@pytest.mark.parametrize("shape,dtype", [((1, 4096, 4096, 8), jnp.bfloat16),
                                         ((2, 48, 128, 1), jnp.float32),
                                         ((1, 4096, 4096, 1), jnp.float32)])
def test_the_gated_group_norm_compiles_at_the_cells_shape(one_chip, shape,
                                                          dtype):
    """The gated norm's value and gradients are two kernels,
    ``mxtpu_gnorm_fwd`` and ``_bwd``, and the program keeps no float32
    (B, T, C) array: what it holds beyond its inputs and outputs is under one
    such array's bytes."""
    bsz, t, c, g = shape
    shaped = lambda *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)

    def loss(x, w, z):
        y = ssm.gated_group_norm(x, w, z, 1e-5, g)
        return (y.astype(jnp.float32) ** 2).sum()
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shaped(bsz, t, c), shaped(c), shaped(bsz, t, c)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "mxtpu_gnorm_fwd" in text and "mxtpu_gnorm_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < bsz * t * c * 4
