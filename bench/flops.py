"""Model FLOPs of a DTFL round on the bottleneck ResNets, from their shapes.

Counts the multiply-adds of the convolutions and linear layers, two FLOPs
each; normalisation, activations, pooling and the optimizer are left out
(a few per cent of the convolutions at these widths). A backward pass
costs two forwards (the gradient with respect to the input and to the
weights), except where no input gradient is needed: the stem, whose input
is the images, and the server's first layers, whose input ``z`` carries no
gradient back to the client. Nothing recomputed and no padded step counts.
"""
from __future__ import annotations

import reference


def _conv(hw: int, k: int, cin: int, cout: int) -> int:
    return 2 * hw * hw * k * k * cin * cout


def block_flops(b: dict, hw_in: int) -> tuple[int, int, int]:
    """(forward FLOPs per sample, of them in the layers that read the block's
    input, output side) of one bottleneck block on an ``hw_in`` square input."""
    hw = -(-hw_in // b["stride"])
    first = _conv(hw_in, 1, b["cin"], b["mid"])          # conv1 reads x
    rest = _conv(hw, 3, b["mid"], b["mid"]) + _conv(hw, 1, b["mid"], b["cout"])
    if b["down"]:
        first += _conv(hw, 1, b["cin"], b["cout"])      # the shortcut reads x
    return first + rest, first, hw


def client_step_flops(arch: dict, tier: int, batch: int) -> int:
    """Forward and backward FLOPs of one client step at ``tier``: the client
    half and its auxiliary head, and the server half and its classifier."""
    size = arch["image_size"]
    stem = _conv(size, 3, 3, arch["width"])
    total, hw = 3 * stem - stem, size                   # no input gradient
    nb = reference.client_blocks(arch, tier)
    for i, b in enumerate(reference.block_plan(arch)):
        fwd, first, hw = block_flops(b, hw)
        # the server's first block reads z, which needs no gradient
        total += 3 * fwd - (first if i == nb else 0)
    head = 2 * reference.head_width(arch, tier) * arch["n_classes"]
    fc = 2 * reference.block_plan(arch)[-1]["cout"] * arch["n_classes"]
    return batch * (total + 3 * head + 3 * fc - (fc if nb == len(reference.block_plan(arch)) else 0))


def forward_flops(arch: dict, n: int) -> int:
    """FLOPs of the whole model's forward pass over ``n`` samples (eval)."""
    size = arch["image_size"]
    total, hw = _conv(size, 3, 3, arch["width"]), size
    for b in reference.block_plan(arch):
        fwd, _, hw = block_flops(b, hw)
        total += fwd
    return n * (total + 2 * reference.block_plan(arch)[-1]["cout"] * arch["n_classes"])
