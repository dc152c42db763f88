"""Plain reference of a DTFL round on the bottleneck ResNets of the paper.

Written from the papers, not from the program: He et al. 2016
(arXiv:1512.03385) for the CIFAR bottleneck ResNet-(9n+2), DTFL
(arXiv:2312.05642) Algorithm 1 and Appendix A.5 for the modules md1..md8,
the split at a tier, the auxiliary head (avgpool + fc) and the local loss,
and Kingma & Ba 2015 for Adam. It imports nothing of ``src/``.

Per round, each participant, one after another:

1. takes the global model's first modules (md1 .. md{tier+1}) as its
   client half and the tier's shared auxiliary head; the server takes
   the rest and the classifier;
2. for each of its batches: the client half and the auxiliary head step on
   the local loss (cross-entropy of the head's logits), the server half
   steps on the cross-entropy of the classifier's logits over the client's
   activations, which carry no gradient back; three Adam states start from
   zero at the start of the round;
3. the halves are joined again.

The global model is the average of the joined models with weights N_k (the
client's sample count), and each tier's head the average of the heads its
members trained; a tier no one trained keeps its head.

Departures from the papers, which the program shares: GroupNorm with 8
groups in place of BatchNorm (federated averaging needs no running
statistics), and no max-pool after the stem (32x32 inputs).

``dtype=float32`` runs every matrix product at HIGHEST precision; the
control runs it all, weights and optimizer state too, in ``bfloat16``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

GN_EPS = 1e-5
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


# ---------------------------------------------------------------------------
# architecture
# ---------------------------------------------------------------------------

def block_plan(arch: dict) -> list[dict]:
    """One entry per bottleneck block: channels, stride and module md2..md7.

    Stage s (0, 1, 2) has mid width w * 2**s and output width 4 * w * 2**s;
    its first block strides (stages 1 and 2) and projects the shortcut; the
    first half of a stage's blocks is module 2 + 2s, the second 3 + 2s.
    """
    n, w = arch["blocks_per_stage"], arch["width"]
    plan, cin = [], w
    for s in range(3):
        mid, cout = w << s, 4 * (w << s)
        for i in range(n):
            plan.append({"cin": cin, "mid": mid, "cout": cout,
                         "stride": (1 if s == 0 else 2) if i == 0 else 1,
                         "down": i == 0,
                         "module": 2 + 2 * s + (0 if i < max(1, n // 2) else 1)})
            cin = cout
    return plan


def n_tiers(arch: dict) -> int:
    return arch["n_modules"] - 1


def client_blocks(arch: dict, tier: int) -> int:
    """Blocks on the client at 0-based ``tier``: those of md2..md{tier+1}."""
    return sum(b["module"] <= tier + 1 for b in block_plan(arch))


def head_width(arch: dict, tier: int) -> int:
    nb = client_blocks(arch, tier)
    return arch["width"] if nb == 0 else block_plan(arch)[nb - 1]["cout"]


# ---------------------------------------------------------------------------
# weights, made from the seed in one jitted call
# ---------------------------------------------------------------------------

def _he(key, k, cin, cout):
    return jax.random.normal(key, (k, k, cin, cout), jnp.float32) * math.sqrt(
        2.0 / (k * k * cin))


def _gn(c):
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def _fc(key, cin, n):
    return {"w": jax.random.normal(key, (cin, n), jnp.float32) * 0.01,
            "b": jnp.zeros((n,), jnp.float32)}


def seed_key(seed: int):
    """A PRNG key for any whole ``seed`` (64-bit seeds fold their high word)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnums=1)
def _init(key, arch_items):
    arch = dict(arch_items)
    plan = block_plan(arch)
    kp, ka = jax.random.split(key)
    ks = jax.random.split(kp, len(plan) + 2)
    blocks = []
    for kb, b in zip(ks[1:-1], plan):
        k1, k2, k3, k4 = jax.random.split(kb, 4)
        p = {"conv1": _he(k1, 1, b["cin"], b["mid"]), "gn1": _gn(b["mid"]),
             "conv2": _he(k2, 3, b["mid"], b["mid"]), "gn2": _gn(b["mid"]),
             "conv3": _he(k3, 1, b["mid"], b["cout"]), "gn3": _gn(b["cout"])}
        if b["down"]:
            p["down"] = _he(k4, 1, b["cin"], b["cout"])
        blocks.append(p)
    params = {"stem": {"conv": _he(ks[0], 3, 3, arch["width"]), "gn": _gn(arch["width"])},
              "blocks": blocks,
              "fc": _fc(ks[-1], plan[-1]["cout"], arch["n_classes"])}
    kt = jax.random.split(ka, n_tiers(arch))
    aux = {t: _fc(kt[t], head_width(arch, t), arch["n_classes"])
           for t in range(n_tiers(arch))}
    return params, aux


def init(seed: int, arch: dict):
    """(global params, {tier: auxiliary head}) drawn from ``seed``: He-normal
    convolutions, unit GroupNorm scales, zero biases, N(0, 0.01^2) heads."""
    return _init(seed_key(seed), tuple(sorted(arch.items())))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def conv(x, w, stride=1):
    """SAME convolution as a sum over the kernel's taps of a strided shift of
    the input times that tap's (cin, cout) matrix."""
    k = w.shape[0]
    n, h, wd, _ = x.shape
    oh, ow = -(-h // stride), -(-wd // stride)
    ph, pw = max((oh - 1) * stride + k - h, 0), max((ow - 1) * stride + k - wd, 0)
    x = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2), (0, 0)))
    out = 0.0
    for i in range(k):
        for j in range(k):
            tap = x[:, i:i + stride * (oh - 1) + 1:stride, j:j + stride * (ow - 1) + 1:stride]
            out = out + tap @ w[i, j]
    return out


def groupnorm(x, p, groups=8):
    n, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(n, h, w, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + GN_EPS)
    return xg.reshape(n, h, w, c) * p["scale"] + p["bias"]


def bottleneck(x, p, stride):
    h = jax.nn.relu(groupnorm(conv(x, p["conv1"]), p["gn1"]))
    h = jax.nn.relu(groupnorm(conv(h, p["conv2"], stride), p["gn2"]))
    h = groupnorm(conv(h, p["conv3"]), p["gn3"])
    if "down" in p:
        x = conv(x, p["down"], stride)
    return jax.nn.relu(x + h)


def classify(p, x):
    """Average pool, then a linear layer (the classifier and the aux head)."""
    return x.mean(axis=(1, 2)) @ p["w"] + p["b"]


def run_blocks(blocks, plans, x):
    """The blocks in order; a run of blocks of one shape is a ``lax.scan``
    over their stacked weights (the same arithmetic, and a program that
    compiles in a fraction of the time of the unrolled one)."""
    i = 0
    while i < len(blocks):
        j = i + 1
        while j < len(blocks) and plans[j] == {**plans[i], "module": plans[j]["module"]}:
            j += 1
        if j - i == 1:
            x = bottleneck(x, blocks[i], plans[i]["stride"])
        else:
            stacked = jax.tree.map(lambda *a: jnp.stack(a), *blocks[i:j])
            x, _ = jax.lax.scan(
                lambda h, p, s=plans[i]["stride"]: (bottleneck(h, p, s), None), x, stacked)
        i = j
    return x


def stem(p, images):
    return jax.nn.relu(groupnorm(conv(images, p["conv"]), p["gn"]))


def forward(params, arch, images):
    x = run_blocks(params["blocks"], block_plan(arch), stem(params["stem"], images))
    return classify(params["fc"], x)


def xent(logits, labels, weight):
    """Mean cross-entropy over the samples whose weight is 1."""
    logits = logits.astype(jnp.float32)
    per = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[:, None], -1)[:, 0]
    return (per * weight).sum() / jnp.maximum(weight.sum(), 1.0)


# ---------------------------------------------------------------------------
# one client step and a round
# ---------------------------------------------------------------------------

def adam(p, g, st):
    t = st["t"] + 1
    m = jax.tree.map(lambda m_, g_: ADAM_B1 * m_ + (1 - ADAM_B1) * g_, st["m"], g)
    v = jax.tree.map(lambda v_, g_: ADAM_B2 * v_ + (1 - ADAM_B2) * g_ * g_, st["v"], g)
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    # the step is worked out in float32 and stored in the weights' own type
    p = jax.tree.map(
        lambda p_, m_, v_: (p_ - st["lr"] * (m_ / c1) / (jnp.sqrt(v_ / c2) + ADAM_EPS)
                            ).astype(p_.dtype),
        p, m, v)
    return p, {"t": t, "lr": st["lr"], "m": m, "v": v}


def adam_init(p, lr):
    return {"t": jnp.zeros((), jnp.float32), "lr": jnp.asarray(lr, jnp.float32),
            "m": jax.tree.map(jnp.zeros_like, p), "v": jax.tree.map(jnp.zeros_like, p)}


@functools.lru_cache(maxsize=None)
def _step(arch_items, tier, dtype_name):
    arch = dict(arch_items)
    plan = block_plan(arch)
    nb = client_blocks(arch, tier)
    dtype = jnp.dtype(dtype_name)
    precision = "highest" if dtype == jnp.float32 else "default"

    def client_loss(cp, ap, batch):
        z = run_blocks(cp["blocks"], plan[:nb], stem(cp["stem"], batch["images"]))
        return xent(classify(ap, z), batch["labels"], batch["mask"]), z

    def server_loss(sp, z, batch):
        x = run_blocks(sp["blocks"], plan[nb:], z)
        return xent(classify(sp["fc"], x), batch["labels"], batch["mask"])

    def step(cp, ap, sp, oc, oa, os_, batch):
        with jax.default_matmul_precision(precision):
            (_, z), (gc, ga) = jax.value_and_grad(
                client_loss, argnums=(0, 1), has_aux=True)(cp, ap, batch)
            gs = jax.grad(server_loss)(sp, jax.lax.stop_gradient(z), batch)
        cp, oc = adam(cp, gc, oc)
        ap, oa = adam(ap, ga, oa)
        sp, os_ = adam(sp, gs, os_)
        return cp, ap, sp, oc, oa, os_

    return jax.jit(step)


def cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
                        else x, tree)


def run_round(arch, params, aux, assignment, batches, weights, *, lr,
              dtype=jnp.float32, devices=None, fault=None, placement=None):
    """One DTFL round. ``assignment`` maps client id -> tier, ``batches``
    client id -> {name: (steps, batch, ...)} arrays, ``weights`` client id ->
    N_k. Returns the new (params, aux) on the first device.

    Client k's steps run on ``devices[i % len(devices)]`` (i its position in
    ``assignment``), so several chips share the work; the weighted sums are
    taken on the first.

    ``fault`` plants a known fault in place of the program, so that what
    the comparison reads for it can be measured: ``"half_batch"`` steps on
    the first half of every batch, ``"half_update"`` moves the model half
    of the way to the average, and ``"no_exchange"`` averages only the
    clients that ``placement`` (client id -> chip) puts on chip 0.
    """
    dtype = jnp.dtype(dtype)
    devices = devices or [jax.devices()[0]]
    arch_items = tuple(sorted(arch.items()))
    params, aux = cast(params, dtype), cast(aux, dtype)
    outs = []
    for i, (k, tier) in enumerate(assignment.items()):
        dev = devices[i % len(devices)]
        nb = client_blocks(arch, tier)
        cp = {"stem": params["stem"], "blocks": params["blocks"][:nb]}
        sp = {"blocks": params["blocks"][nb:], "fc": params["fc"]}
        state = jax.device_put((cp, aux[tier], sp), dev)
        state = state + tuple(adam_init(x, lr) for x in state)
        step = _step(arch_items, tier, dtype.name)
        b = batches[k]
        for s in range(len(b["labels"])):
            batch = {n: b[n][s] for n in ("images", "labels", "mask")}
            if fault == "half_batch":
                batch = {n: v[: len(v) // 2] for n, v in batch.items()}
            batch["images"] = batch["images"].astype(dtype)
            state = step(*state, jax.device_put(batch, dev))
        cp, ap, sp = state[:3]
        outs.append((k, tier, {"stem": cp["stem"], "blocks": cp["blocks"] + sp["blocks"],
                               "fc": sp["fc"]}, ap))
    if fault == "no_exchange":
        outs = [o for o in outs if placement[o[0]] == 0]
    dev0 = devices[0]
    total = sum(weights[k] for k, *_ in outs)
    new = _wsum([(weights[k], jax.device_put(m, dev0)) for k, _, m, _ in outs], total)
    new_aux = dict(aux)
    for tier in sorted({t for _, t, *_ in outs}):
        mem = [(weights[k], jax.device_put(a, dev0)) for k, t, _, a in outs if t == tier]
        new_aux[tier] = _wsum(mem, sum(w for w, _ in mem))
    if fault == "half_update":
        half = lambda old, n: jax.tree.map(lambda o, x: o + (x - o) / 2, old, n)
        new = half(jax.device_put(params, dev0), new)
        new_aux = {t: half(jax.device_put(aux[t], dev0), a) for t, a in new_aux.items()}
    return new, new_aux


def _wsum(items, total):
    acc = None
    for w, tree in items:
        part = jax.tree.map(lambda x: x.astype(jnp.float32) * (w / total), tree)
        acc = part if acc is None else jax.tree.map(jnp.add, acc, part)
    return acc


@functools.lru_cache(maxsize=None)
def _eval_fn(arch_items):
    arch = dict(arch_items)

    def f(params, images, labels):
        with jax.default_matmul_precision("highest"):
            logits = forward(cast(params, jnp.float32), arch, images)
        return logits, xent(logits, labels, jnp.ones(labels.shape, jnp.float32))

    return jax.jit(f)


def evaluate(arch, params, images, labels):
    """(logits, mean cross-entropy) on an evaluation batch, at HIGHEST."""
    logits, loss = _eval_fn(tuple(sorted(arch.items())))(params, images, labels)
    return np.asarray(logits, np.float64), float(loss)
