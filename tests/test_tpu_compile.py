"""The main path's Pallas kernels, compiled for a described TPU v5e at
real widths. Nothing runs: the TPU's compiler is installed here and
compiles for a chip that is described, not attached, so what it refuses
(a misaligned slice, too much VMEM) is caught at no chip time.

This is the ONE file that describes the chip. The topology is described
inside a module-scoped fixture, after a test of this file has started:
only one process at a time may load the TPU's library, and every xdist
worker imports every test file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.pallas.flash_attention import flash_attention
from mxnet_tpu.pallas.fused_bn import scale_bias_add_relu


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip can be written to JAX's persistent
    cache but not read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _sum32(fn):
    return lambda *a: jnp.sum(fn(*a).astype(jnp.float32))


# the four ResNet-50 NHWC stage outputs at batch 128
@pytest.mark.parametrize("shape", [(128, 56, 56, 256), (128, 28, 28, 512),
                                   (128, 14, 14, 1024), (128, 7, 7, 2048)],
                         ids=lambda s: "x".join(map(str, s)))
def test_fused_bn_compiles_at_resnet50_stage(one_chip, shape):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct(shape[-1:], jnp.float32, sharding=one_chip)

    def fused(x, s, b, r):
        return scale_bias_add_relu(x, s, b, r, interpret=False)

    fwd = _compile(fused, x, v, v, x)
    assert "tpu_custom_call" in fwd.as_text()
    grad = _compile(jax.grad(_sum32(fused), argnums=(0, 1, 2, 3)),
                    x, v, v, x)
    assert "tpu_custom_call" in grad.as_text()


def _attn(causal):
    return lambda q, k, v: flash_attention(q, k, v, causal, None, 128, False)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_attention_forward_compiles(one_chip, causal):
    q = jax.ShapeDtypeStruct((4, 16, 2048, 128), jnp.bfloat16,
                             sharding=one_chip)
    assert "tpu_custom_call" in _compile(_attn(causal), q, q, q).as_text()


def test_flash_attention_gradient_compiles(one_chip):
    q = jax.ShapeDtypeStruct((4, 16, 2048, 128), jnp.bfloat16,
                             sharding=one_chip)
    grad = _compile(jax.grad(_sum32(_attn(True)), argnums=(0, 1, 2)),
                    q, q, q)
    assert "tpu_custom_call" in grad.as_text()


@pytest.mark.parametrize("window,kv_heads,d,dv", [
    (None, 4, 128, 128), (2048, 4, 128, 128), (None, 32, 192, 128)],
    ids=["full", "window", "latent_192x128"])
def test_flash_attention_compiles_at_8k(one_chip, window, kv_heads, d, dv):
    """The plain entry tiles over K/V: at S=8192, D=128 with 32 query
    heads on 4 K/V heads (the language-model cell's attention) the causal
    forward and the gradient compile, with and without the window. (Until
    the PR that tiled it, the whole local K/V block and a (block_q, S_kv)
    score tile sat in VMEM and this shape was refused.) And latent
    attention's shape: 32 heads with keys of 192, one and a half lane
    tiles, taken as they are, and values of 128."""
    q = jax.ShapeDtypeStruct((1, 32, 8192, d), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, kv_heads, 8192, d), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, kv_heads, 8192, dv), jnp.bfloat16,
                             sharding=one_chip)

    def attn(q, k, v):
        return flash_attention(q, k, v, True, None, None, False, window)

    assert "tpu_custom_call" in _compile(attn, q, k, v).as_text()
    grad = _compile(jax.grad(_sum32(attn), argnums=(0, 1, 2)), q, k, v)
    assert grad.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("window", [None, 2048], ids=["full", "window"])
def test_mirrored_layers_run_the_forward_kernel_once(one_chip, window):
    """Two layers of projections + attention + gate at the language-model
    cell's shapes, each under the checkpoint a mirrored segment gets
    (``executor._MIRROR_POLICY``): the gradient's program holds one
    forward kernel a layer (a bare checkpoint makes each again), and what
    the segments hold for the backward pass is their inputs, each layer's
    output of the kernel (64 MiB) and its log-sum-exp compact: 1 MiB a
    layer, where the kernel's own (..., 1) form is padded to 128 MiB."""
    from mxnet_tpu import executor
    t, hq, hkv, d, f = 8192, 32, 4, 128, 2048

    def shape(*s):
        return jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)

    def layer(x, wq, wk, wv, wg, wo):
        def heads(y, h):
            return y.reshape(1, t, h, d).transpose(0, 2, 1, 3)
        o = flash_attention(heads(x @ wq, hq), heads(x @ wk, hkv),
                            heads(x @ wv, hkv), True, None, None, False,
                            window)
        o = o.transpose(0, 2, 1, 3).reshape(1, t, hq * d)
        return x + (o * jax.nn.sigmoid(x @ wg)) @ wo

    def net(x, *ws):
        for i in range(2):
            x = jax.checkpoint(layer, policy=executor._MIRROR_POLICY)(
                x, *ws[5 * i:5 * i + 5])
        return jnp.sum(x.astype(jnp.float32))

    args = (shape(1, t, f),) + (shape(f, hq * d), shape(f, hkv * d),
                                shape(f, hkv * d), shape(f, hq * d),
                                shape(hq * d, f)) * 2
    grad = _compile(jax.grad(net, argnums=tuple(range(11))), *args)
    text = grad.as_text()
    for kernel, count in (("fwd", 2), ("dq", 2), ("dkv", 2)):
        assert len([line for line in text.splitlines()
                    if " custom-call(" in line
                    and "flash_attention_" + kernel in line]) == count
    # what a segment hands the backward pass: the log-sum-exp as (B, Hq, S)
    held = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda *a: jax.vjp(net, *a), *args))
    assert sum(h.shape == (1, hq, t) and h.dtype == jnp.float32
               for h in held) == 2
    assert sum(h.shape == (1, hq, t, d) for h in held) == 2
    assert not any(h.shape == (1, hq, t, 1) for h in held)
    # and the chip's buffers say so: 677.0 MiB of temporaries, against
    # 804.5 when the log-sum-exp crosses a segment in the kernel's form
    # (both compiled here, full and windowed alike)
    assert grad.memory_analysis().temp_size_in_bytes < 700 * 2 ** 20


def test_flash_attention_carry_vmem_limit_at_8k(one_chip):
    """The carry entry (ring attention's building block) still holds the
    whole local K/V block and a (block_q, S_kv) f32 score tile in VMEM
    (flash_attention.py docstring), so a local block of 8192 is refused:
    sequence parallelism is meant to keep it short."""
    from mxnet_tpu.pallas.flash_attention import flash_attention_carry
    q = jax.ShapeDtypeStruct((16, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    o = jax.ShapeDtypeStruct((16, 8192, 128), jnp.float32, sharding=one_chip)
    m = jax.ShapeDtypeStruct((16, 8192), jnp.float32, sharding=one_chip)

    def carry(q, k, v, o, m, l):
        return flash_attention_carry(q, k, v, o, m, l, causal=True,
                                     interpret=False)

    with pytest.raises(Exception, match="(?i)vmem|RESOURCE_EXHAUSTED"):
        _compile(carry, q, q, q, o, m, m)


@pytest.mark.parametrize("cell", [
    # top-k, d, f, held, gated, GiB of temporaries
    (8, 2048, 1024, 16, True, 2.1), (6, 2688, 1856, 8, False, 2.6)],
    ids=["trinity_mini.fit", "nemotron3_nano.fit"])
def test_chunked_moe_layer_compiles_at_the_cell_shapes(one_chip, cell):
    """The routed layer at the cells' shapes (8,192 tokens, bfloat16;
    ``trinity_mini.fit``: top-8, d 2,048, f 1,024, 16 of 128 gated experts
    held; ``nemotron3_nano.fit``: top-6, 2,688 x 1,856, 8 of 128, no
    gate): forward and gradient compile, the sorted order in chunks of
    the even share (8 of 8,192 rows, 16 of 3,072). The grouped products stay 3
    forward and 3 + 6 with the gradient (2 and 2 + 4 without a gate), each
    one kernel over the whole order; the row passes are loops whose trip
    count is the step's own, and no branch holds a second body; the
    products' row tile is the 512 rows that ``chunk_rows`` rounds to.
    Under ``jax.checkpoint``, as the step holds a layer, the gated layer's
    temporaries stay at the whole-buffer layer's 2.01 GB (1.93 GiB; 2.44
    GiB for the ungated layer at its wider, padded shapes)."""
    from mxnet_tpu.parallel import moe
    t, n = 8192, 128
    k, d, f, count, gated, room = cell
    assert moe.chunk_rows(t * k, count, n) == t * k * count // n

    def shape(*s):
        return jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)

    args = (shape(t, d), shape(n, d), shape(count, d, f)) \
        + (shape(count, d, f),) * gated + (shape(count, f, d),)

    def layer(x, router, w1, *w):
        return moe.moe_layer(x, router, jnp.zeros(n), w1, *(None,) * (not gated),
                             *w, k, (0, count), route_scale=2.826,
                             act="silu" if gated else "relu2")[0]

    def step(*a):
        out, pull = jax.vjp(jax.checkpoint(layer), *a)
        return out, pull(jnp.cos(out))

    # the suite's "highest" is for float32 parity on the CPU; the chip's
    # grouped product takes bfloat16 operands at the default precision only
    with jax.default_matmul_precision("default"):
        fwd = _compile(layer, *args)
        grad = _compile(step, *args)
    products = 3 if gated else 2
    assert fwd.as_text().count('op_name="ragged-dot-none"') == products
    text = grad.as_text()
    assert text.count('op_name="ragged-dot-none"') == 4 * products
    assert " while(" in text and " conditional(" not in text
    # the grouped product walks its rows in tiles of ``moe._TILE``: its
    # metadata lists T k / tile + count - 1 of them. A chunk is a multiple
    # of the tile, so the tile that holds the last held row ends less than
    # a tile past it, and the row passes keep that far nought
    tiles = t * k // moe._TILE + count - 1
    metadata = [line for line in text.splitlines()
                if 'op_name="ragged-dot-metadata"' in line
                and "custom-call(" in line]
    assert metadata and all("s32[%d]" % tiles in m for m in metadata)
    assert grad.memory_analysis().temp_size_in_bytes < room * 2 ** 30


def test_ssd_compiles_at_the_cell_shapes(one_chip, monkeypatch):
    """``_contrib_SSD`` at ``nemotron3_nano.fit``'s shapes (one sequence of
    8,192 tokens, 64 heads of 64, state 128 in 8 groups, chunks of 128,
    bfloat16), forward and gradient under ``jax.checkpoint`` as the step
    holds a block: the Pallas kernels compile (one forward, one backward:
    the recomputed forward is not needed for the kernel's own residuals),
    no decay or score block of ``(chunk, chunk)`` a head a chunk exists
    outside them, no loop over tokens or chunks (the pass of the states is
    the L-form over chunks, a product), and the temporaries of one mixer's
    recurrence stay under 0.7 GB."""
    from mxnet_tpu.ops import lm
    from mxnet_tpu.pallas import ssd as kernels
    monkeypatch.setattr(kernels, "_use_interpret", lambda: False)
    t, h, p, n, g = 8192, 64, 64, 128, 8

    def shape(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    args = (shape(1, t, h * p), shape(1, t, h), shape(1, t, g * n),
            shape(1, t, g * n)) + (shape(h, dtype=jnp.float32),) * 3

    def ssd(*a):
        return lm.ssd(*a, heads=h, head_dim=p, state=n,
                      groups=g, chunk=128)

    def step(*a):
        out, pull = jax.vjp(jax.checkpoint(ssd), *a)
        return out, pull(jnp.cos(out))

    with jax.default_matmul_precision("default"):
        grad = _compile(step, *args)
    text = grad.as_text()
    calls = [line for line in text.splitlines() if " custom-call(" in line]
    assert sum("ssd_chunk_fwd" in c for c in calls) == 1
    assert sum("ssd_chunk_bwd" in c for c in calls) == 1
    assert " while(" not in text
    assert "f32[1,64,64,128,128]" not in text
    assert "f32[1,64,8,8,128,128]" not in text
    assert "f32[64,8,8,128,128]" not in text
    assert grad.memory_analysis().temp_size_in_bytes < 0.7 * 2 ** 30


def test_kda_compiles_at_the_cell_shapes(one_chip, monkeypatch):
    """``_contrib_KDA`` at ``kimi_linear.fit``'s shapes (one sequence of
    8,192 tokens, 32 heads of 128 for keys and values, chunks of 64 in
    sub-blocks of 16, bfloat16), forward and gradient under
    ``jax.checkpoint`` as the step holds a layer: ``kda/intra`` is the
    Pallas kernels of ``pallas/kda.py`` (the forward twice: the pull-back
    of what follows it needs its outputs; the backward once), no block of
    the sub-blocks' exponentials exists outside them, the only loop is the
    one over chunks that carries the state, and the temporaries of one
    layer's recurrence stay under 2.3 GiB (2.08 when this was written;
    3.02 with the blocks and the inverse in ``jax.numpy`` a slab of 16
    chunks at a time, 4.77 with the exponentials whole)."""
    from mxnet_tpu.ops import lm
    from mxnet_tpu.pallas import kda as kernels
    monkeypatch.setattr(kernels, "_use_interpret", lambda: False)
    t, h, d = 8192, 32, 128

    def shape(*s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    args = (shape(1, t, h * d),) * 4 + (
        shape(1, t, h), shape(h, dtype=jnp.float32),
        shape(h * d, dtype=jnp.float32))

    def kda(*a):
        return lm.kda(*a, heads=h, chunk=64, sub=16)

    def step(*a):
        out, pull = jax.vjp(jax.checkpoint(kda), *a)
        return out, pull(jnp.cos(out))

    with jax.default_matmul_precision("default"):
        grad = _compile(step, *args)
    text = grad.as_text()
    calls = [line for line in text.splitlines() if " custom-call(" in line]
    assert sum("kda_intra_fwd" in c for c in calls) == 2
    assert sum("kda_intra_bwd" in c for c in calls) == 1
    assert "16,16,128]" not in text
    assert text.count(" while(") == 3       # forward, recomputed, pull-back
    assert grad.memory_analysis().temp_size_in_bytes < 2.3 * 2 ** 30
