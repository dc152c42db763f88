"""Paper-native ResNet path: module splits, aux heads, Table-10 channels."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.resnet_cifar import RESNET56, RESNET110, get_resnet
from repro.core import splitting
from repro.models import resnet as R


def _split(p, cfg, tier_module):
    nb = R.n_blocks_in_modules(cfg, tier_module)
    return splitting.split_params(p, nb, splitting.RESNET)


@pytest.mark.parametrize("cfg", [RESNET56.reduced(), RESNET56, RESNET110])
def test_forward_and_splits(cfg, key):
    p = R.init(key, cfg)
    x = jax.random.normal(key, (2, cfg.image_size, cfg.image_size, 3))
    want = R.forward(p, cfg, x)
    assert want.shape == (2, cfg.n_classes)
    for tier in range(1, cfg.n_modules):
        c, s = _split(p, cfg, tier)
        z = R.client_forward(c, cfg, x)
        got = R.server_forward(s, cfg, z, tier)
        np.testing.assert_allclose(want, got, atol=1e-4)
        aux = R.aux_apply(R.aux_init(key, cfg, tier), z)
        assert aux.shape == (2, cfg.n_classes)


def test_block_plan_56_110_depths():
    # ResNet-6n+2 bottleneck: 56 -> n=6 per stage; 110 -> n=12
    assert len(R._block_plan(RESNET56)) == 18
    assert len(R._block_plan(RESNET110)) == 36


def test_table10_aux_channels():
    """Aux fc input widths per tier must follow Table 10 (16,64,64,128,128,256,256
    for the paper's width-16 stacks)."""
    w = RESNET56.width
    chans = [R.aux_channels(RESNET56, t) for t in range(1, 8)]
    assert chans == [w, 4 * w, 4 * w, 8 * w, 8 * w, 16 * w, 16 * w]


def test_merge_roundtrip(key):
    cfg = RESNET56.reduced()
    p = R.init(key, cfg)
    c, s = _split(p, cfg, 2)
    m = splitting.merge_params(c, s, splitting.RESNET)
    assert jax.tree.all(jax.tree.map(jnp.array_equal, p, m))


def test_module_boundaries_cover_all_blocks():
    for cfg in (RESNET56, RESNET110):
        assert R.n_blocks_in_modules(cfg, 7) == cfg.n_blocks
        assert R.n_blocks_in_modules(cfg, 1) == 0  # md1 is the stem only


def _assert_close(got, want, rtol=1e-5):
    """Each leaf within ``rtol`` of the largest magnitude of ``want``'s."""
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape
        assert np.abs(a - b).max() <= rtol * np.abs(b).max()


@pytest.mark.parametrize("cin", [3, 16])
@pytest.mark.parametrize("hw", [8, 7])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("k", [1, 3])
def test_native_conv_equals_im2col(k, stride, hw, cin):
    """The accelerators' lowering (the conv primitive) computes what the
    CPU's im2col does, forward and gradient, plain and vmapped over two
    clients' weights as the cohort engine runs it."""
    kx, kw = jax.random.split(jax.random.PRNGKey(k * 100 + stride * 10 + hw + cin))
    x = jax.random.normal(kx, (2, 4, hw, hw, cin))           # (clients, N, H, W, C)
    w = R.conv_init(kw, k, cin, 8)[None] * jnp.array([1.0, 0.5])[:, None, None, None, None]

    def fwd(conv):
        return lambda x, w: conv(x, w, stride)

    def grad(conv):
        return jax.grad(lambda x, w: jnp.sum(jnp.sin(conv(x, w, stride))), argnums=(0, 1))

    for f in (fwd, grad):
        native, im2col = f(R.conv_native), f(R.conv_im2col)
        _assert_close(jax.jit(native)(x[0], w[0]), jax.jit(im2col)(x[0], w[0]))
        _assert_close(jax.jit(jax.vmap(native))(x, w), jax.jit(jax.vmap(im2col))(x, w))


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("k", [1, 3])
def test_conv_on_the_cpu_is_im2col(k, stride):
    """On the CPU backend ``conv`` is the im2col form bit for bit."""
    assert jax.default_backend() == "cpu"
    kx, kw = jax.random.split(jax.random.PRNGKey(k + stride))
    x = jax.random.normal(kx, (2, 4, 9, 9, 16))
    w = jax.random.normal(kw, (2, k, k, 16, 8))
    got = jax.jit(jax.vmap(lambda x, w: R.conv(x, w, stride)))(x, w)
    want = jax.jit(jax.vmap(lambda x, w: R.conv_im2col(x, w, stride)))(x, w)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend,precision,native", [
    ("tpu", None, True),
    ("tpu", "default", True),
    ("tpu", "highest", True),
    ("gpu", "float32", True),
    ("cpu", None, False),
])
def test_conv_lowering_follows_backend_and_precision(backend, precision, native, monkeypatch):
    """``conv`` takes the conv primitive on any accelerator, whatever the
    matmul precision, and im2col on the CPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    x, w = jnp.zeros((2, 8, 8, 16)), jnp.zeros((3, 3, 16, 8))
    with jax.default_matmul_precision(precision):
        jaxpr = str(jax.make_jaxpr(lambda x, w: R.conv(x, w))(x, w))  # a new trace
    assert ("conv_general_dilated" in jaxpr) == native
    assert ("concatenate" in jaxpr) != native
