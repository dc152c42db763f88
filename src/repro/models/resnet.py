"""Paper-native ResNet-56/110 (bottleneck) with the DTFL md1..md8 modules.

Faithful to DTFL Appendix A.5 (Tables 8/9/10):
  md1  = stem conv (3->16) [+ maxpool]
  md2  = stage-1 first half (incl. the 16->64 downsample bottleneck)
  md3  = stage-1 second half
  md4  = stage-2 first half (64->128, stride 2)
  md5  = stage-2 second half
  md6  = stage-3 first half (128->256, stride 2)
  md7  = stage-3 second half
  md8  = avgpool + fc
Auxiliary network per tier = avgpool + fc(channels_of_split -> n_classes),
exactly Table 10.

Deviation (DESIGN.md §8): BatchNorm is replaced with GroupNorm(8) so
federated averaging needs no running-stats bookkeeping — a standard FL
substitution; the paper's own FedMA/BN discussion is unaffected.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

Params = Any


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def conv_init(key, k: int, cin: int, cout: int) -> jax.Array:
    fan_in = k * k * cin
    return jax.random.normal(key, (k, k, cin, cout), jnp.float32) * math.sqrt(2.0 / fan_in)


def conv(x: jax.Array, w: jax.Array, stride: int = 1) -> jax.Array:
    """SAME conv, NHWC input and HWIO weights; the lowering is chosen at
    trace time from the backend.

    On an accelerator it is the conv primitive (``conv_native``). The im2col
    form writes nine copies of every 3x3 conv's input, each padded to 128
    lanes on a TPU, and their gradients in the backward pass; the conv
    primitive reads the input in place, which halves the bytes a ResNet
    block moves on a v5e. Under the cohort engine's per-client ``jax.vmap``
    XLA keeps the client axis as a batch dimension of the convolution.

    On the CPU it stays im2col + matmul (``conv_im2col``), so that the CPU
    tests' bit-equality between execution planes and cohort widths rests on
    the lowering it was pinned on. The two lowerings agree to float32
    rounding, and the planes agree under either (``tests/test_resnet.py``,
    ``tests/test_native_conv_planes.py``).
    """
    if jax.default_backend() == "cpu":
        return conv_im2col(x, w, stride)
    return conv_native(x, w, stride)


def conv_native(x: jax.Array, w: jax.Array, stride: int = 1) -> jax.Array:
    """SAME conv as the conv primitive (SAME puts ``pad // 2`` before, as
    ``conv_im2col`` does)."""
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def conv_im2col(x: jax.Array, w: jax.Array, stride: int = 1) -> jax.Array:
    """SAME conv as im2col + matmul: pad, take k*k strided slices,
    concatenate them into k*k*cin channels, one matmul."""
    k, _, cin, cout = w.shape
    _, H, W, _ = x.shape
    if k == 1:
        return x[:, ::stride, ::stride, :] @ w.reshape(cin, cout)
    oh, ow = -(-H // stride), -(-W // stride)
    ph = max((oh - 1) * stride + k - H, 0)
    pw = max((ow - 1) * stride + k - W, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2), (0, 0)))
    cols = [
        xp[:, i : i + stride * (oh - 1) + 1 : stride,
            j : j + stride * (ow - 1) + 1 : stride, :]
        for i in range(k)
        for j in range(k)
    ]
    return jnp.concatenate(cols, axis=-1) @ w.reshape(k * k * cin, cout)


def groupnorm(x: jax.Array, scale, bias, groups: int = 8, eps: float = 1e-5) -> jax.Array:
    N, H, W, C = x.shape
    g = min(groups, C)
    while C % g:
        g -= 1
    xg = x.reshape(N, H, W, g, C // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    return xg.reshape(N, H, W, C) * scale + bias


def gn_init(c: int) -> Params:
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


# ---------------------------------------------------------------------------
# bottleneck block
# ---------------------------------------------------------------------------

def bottleneck_init(key, cin: int, mid: int, cout: int, downsample: bool) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "conv1": conv_init(ks[0], 1, cin, mid),
        "gn1": gn_init(mid),
        "conv2": conv_init(ks[1], 3, mid, mid),
        "gn2": gn_init(mid),
        "conv3": conv_init(ks[2], 1, mid, cout),
        "gn3": gn_init(cout),
    }
    if downsample:
        p["down"] = conv_init(ks[3], 1, cin, cout)
    return p


def bottleneck_apply(x: jax.Array, p: Params, stride: int) -> jax.Array:
    h = jax.nn.relu(groupnorm(conv(x, p["conv1"]), **p["gn1"]))
    h = jax.nn.relu(groupnorm(conv(h, p["conv2"], stride), **p["gn2"]))
    h = groupnorm(conv(h, p["conv3"]), **p["gn3"])
    if "down" in p:
        x = conv(x, p["down"], stride)
    return jax.nn.relu(x + h)


# ---------------------------------------------------------------------------
# full network
# ---------------------------------------------------------------------------

def _block_plan(cfg) -> list[dict]:
    """One entry per bottleneck block: channels, stride, module id (2..7)."""
    n = cfg.blocks_per_stage
    w = cfg.width
    plan = []
    cin = w
    for stage, (mid, cout, stride) in enumerate(
        [(w, 4 * w, 1), (2 * w, 8 * w, 2), (4 * w, 16 * w, 2)]
    ):
        for i in range(n):
            plan.append(
                dict(
                    cin=cin,
                    mid=mid,
                    cout=cout,
                    stride=stride if i == 0 else 1,
                    down=(i == 0),
                    module=2 + 2 * stage + (0 if i < max(1, n // 2) else 1),
                )
            )
            cin = cout
    return plan


def init(key, cfg) -> Params:
    plan = _block_plan(cfg)
    ks = jax.random.split(key, len(plan) + 2)
    return {
        "stem": {"conv": conv_init(ks[0], 3, 3, cfg.width), "gn": gn_init(cfg.width)},
        "blocks": [
            bottleneck_init(ks[i + 1], b["cin"], b["mid"], b["cout"], b["down"])
            for i, b in enumerate(plan)
        ],
        "fc": {
            "w": jax.random.normal(ks[-1], (16 * cfg.width, cfg.n_classes), jnp.float32) * 0.01,
            "b": jnp.zeros((cfg.n_classes,), jnp.float32),
        },
    }


def module_of_block(cfg, i: int) -> int:
    return _block_plan(cfg)[i]["module"]


def n_blocks_in_modules(cfg, upto_module: int) -> int:
    """Number of bottleneck blocks contained in modules md2..md{upto}."""
    return sum(1 for b in _block_plan(cfg) if b["module"] <= upto_module)


def forward_features(params: Params, cfg, images: jax.Array, upto_module: int = 8) -> jax.Array:
    """Run stem + blocks of modules <= upto_module. images: (B,H,W,3)."""
    x = jax.nn.relu(groupnorm(conv(images, params["stem"]["conv"]), **params["stem"]["gn"]))
    for bp, plan in zip(params["blocks"], _block_plan(cfg)):
        if plan["module"] > upto_module:
            break
        x = bottleneck_apply(x, bp, plan["stride"])
    return x


def head_apply(params: Params, x: jax.Array) -> jax.Array:
    pooled = x.mean(axis=(1, 2))
    return pooled @ params["fc"]["w"] + params["fc"]["b"]


def forward(params: Params, cfg, images: jax.Array) -> jax.Array:
    return head_apply(params, forward_features(params, cfg, images, 8))


# ---------------------------------------------------------------------------
# DTFL split: client modules [1..m], server modules (m..8], aux = avgpool+fc
# (tree split/merge mechanics live in core/splitting.py; boundary policy here)
# ---------------------------------------------------------------------------

def client_forward(client: Params, cfg, images: jax.Array) -> jax.Array:
    x = jax.nn.relu(groupnorm(conv(images, client["stem"]["conv"]), **client["stem"]["gn"]))
    plan = _block_plan(cfg)
    for bp, pl in zip(client["blocks"], plan):
        x = bottleneck_apply(x, bp, pl["stride"])
    return x


def server_forward(server: Params, cfg, z: jax.Array, tier_module: int) -> jax.Array:
    plan = _block_plan(cfg)[n_blocks_in_modules(cfg, tier_module):]
    x = z
    for bp, pl in zip(server["blocks"], plan):
        x = bottleneck_apply(x, bp, pl["stride"])
    return head_apply({"fc": server["fc"]}, x)


def aux_channels(cfg, tier_module: int) -> int:
    """Channel width at the output of module ``tier_module`` (Table 10 fc input)."""
    nb = n_blocks_in_modules(cfg, tier_module)
    if nb == 0:
        return cfg.width
    return _block_plan(cfg)[nb - 1]["cout"]


def aux_init(key, cfg, tier_module: int) -> Params:
    c = aux_channels(cfg, tier_module)
    return {
        "w": jax.random.normal(key, (c, cfg.n_classes), jnp.float32) * 0.01,
        "b": jnp.zeros((cfg.n_classes,), jnp.float32),
    }


def aux_apply(aux: Params, z: jax.Array) -> jax.Array:
    pooled = z.mean(axis=(1, 2))  # avgpool
    return pooled @ aux["w"] + aux["b"]
