"""The Pallas kernels that can be reached on the chip, compiled for a
described TPU v5e (no chip attached): Mosaic refuses what interpret mode
accepts — blocks off the (8, 128) tiling, more VMEM than a kernel may use —
so these compiles guard the chip path at no chip time. The ResNet's chip
lowering of its convolutions is compiled here too, its bytes weighed
against the im2col form's, and so is the DTFL sharded program with it over
the four described chips. Nothing runs here.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers must all collect
the same tests.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.fed import DTFLTrainer, ExecPlan
from repro.fed import cohort as cohort_engine
from repro.kernels import ops
from repro.launch.hlo_analysis import flat_cost_analysis
from repro.launch.mesh import CLIENT_AXIS
from repro.models import resnet as R


@pytest.fixture(scope="module")
def v5e():
    """A described v5e:2x2 (four chips)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip cannot be read back from
    # the persistent cache without one; keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return topo


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_int8_roundtrip_compiles_at_activation_shape(one_chip):
    # a ResNet stage-3 activation batch: (batch, 16, 16, 64) f32, far
    # longer than one block
    _compile(ops.int8_roundtrip_op, one_chip, ((64, 16, 16, 64), jnp.float32))


def test_fused_xent_compiles_at_real_vocab(one_chip):
    # smollm-360m's vocab (configs/smollm_360m.py), 4 x 256 tokens
    _compile(ops.fused_xent_op, one_chip,
             ((4, 256, 49152), jnp.float32), ((4, 256), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_compiles_at_head_dim_64(one_chip, dtype):
    qkv = ((2, 1024, 8, 64), dtype)
    _compile(ops.flash_attention_op, one_chip, qkv, qkv, qkv)


def test_native_conv_block_compiles_and_moves_fewer_bytes(one_chip, monkeypatch):
    # a stage-1 bottleneck block of ResNet-56/110 (32x32, 64->16->64),
    # forward and backward with respect to its parameters and its input,
    # vmapped over 2 clients x 32 images as the cohort programs run it
    params = jax.eval_shape(lambda: R.bottleneck_init(jax.random.PRNGKey(0), 64, 16, 64, False))
    x = jax.ShapeDtypeStruct((2, 32, 32, 32, 64), jnp.float32, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((2, *a.shape), a.dtype, sharding=one_chip), params)

    def compiled_with(conv):
        monkeypatch.setattr(R, "conv", conv)

        def step(p, x):  # a new function each time: jit caches traces by function
            loss = lambda p, x: jnp.sum(R.bottleneck_apply(x, p, 1) ** 2)
            return jax.vmap(jax.grad(loss, argnums=(0, 1)))(p, x)

        return jax.jit(step).lower(params, x).compile()

    native, im2col = compiled_with(R.conv_native), compiled_with(R.conv_im2col)
    # XLA lowers matmuls to convolutions on a TPU too: look for the 3x3 window
    assert "convolution" in native.as_text()
    assert "window={size=3x3" in native.as_text()
    assert "window={size=3x3" not in im2col.as_text()
    native_bytes = flat_cost_analysis(native)["bytes accessed"]
    im2col_bytes = flat_cost_analysis(im2col)["bytes accessed"]
    assert native_bytes < 0.7 * im2col_bytes   # 1.06 GB against 2.38 GB


def test_sharded_dtfl_program_compiles_with_native_conv(v5e, monkeypatch):
    # the DTFL sharded plane as a 4-chip mesh runs it: shard_map over the
    # client axis, the cohort body vmapped inside, the conv primitive, and
    # the psums of the weighted sums (reduced ResNet-56, 4 clients, tier 1)
    from test_tracing import TIER, build, trainer

    monkeypatch.setattr(R, "conv", R.conv_native)
    mesh = Mesh(np.array(v5e.devices), (CLIENT_AXIS,))
    adapter, clients, _ = build()
    tr = trainer(DTFLTrainer, adapter, clients, ExecPlan.sharded(mesh), scheduler=TIER)
    (co,) = cohort_engine.build_cohorts(
        clients, list(range(4)), {k: TIER for k in range(4)}, 0, 1,
        pad_multiple=tr.exec_plan.pad_multiple)

    def placed(tree, spec):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)), tree)

    weights = np.ones(co.mask.shape[1], np.float32)
    text = tr._sharded_program(TIER).lower(
        placed(tr.params, P()), placed(tr.aux[TIER], P()),
        placed(co.batches, P(None, CLIENT_AXIS)), placed(co.mask, P(None, CLIENT_AXIS)),
        placed(weights, P(CLIENT_AXIS))).compile().as_text()
    assert "window={size=3x3" in text
    assert "all-reduce" in text
