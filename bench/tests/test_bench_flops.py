"""The FLOP count against a count by hand."""
import tinycell  # noqa: F401  (puts bench/ on the path)

import flops
import reference

R110 = {"blocks_per_stage": 12, "width": 16, "n_classes": 10, "image_size": 32,
        "n_modules": 8}


def test_first_bottleneck_block_by_hand():
    # stage 0, block 0 of ResNet-110 on a 32x32 input: 16 -> 16 -> 16 -> 64,
    # stride 1, with the 1x1 shortcut 16 -> 64
    b = reference.block_plan(R110)[0]
    assert (b["cin"], b["mid"], b["cout"], b["stride"], b["down"]) == (16, 16, 64, 1, True)
    hw = 32 * 32
    conv1 = 2 * hw * 16 * 16
    conv2 = 2 * hw * 9 * 16 * 16
    conv3 = 2 * hw * 16 * 64
    down = 2 * hw * 16 * 64
    fwd, first, out_hw = flops.block_flops(b, 32)
    assert fwd == conv1 + conv2 + conv3 + down == 9_437_184
    assert first == conv1 + down
    assert out_hw == 32


def test_strided_block_by_hand():
    # stage 1, block 0: 64 -> 32 -> 32 -> 128 on 32x32, stride 2 (16x16 out);
    # conv1 reads the 32x32 input, the shortcut strides
    b = reference.block_plan(R110)[12]
    fwd, first, out_hw = flops.block_flops(b, 32)
    conv1 = 2 * 32 * 32 * 64 * 32
    conv2 = 2 * 16 * 16 * 9 * 32 * 32
    conv3 = 2 * 16 * 16 * 32 * 128
    down = 2 * 16 * 16 * 64 * 128
    assert (fwd, first, out_hw) == (conv1 + conv2 + conv3 + down, conv1 + down, 16)


def test_aux_head_by_hand():
    # tier 0 keeps only the stem on the client: its head reads 16 channels;
    # tier 6 keeps every block: 256 channels, 10 classes
    assert reference.head_width(R110, 0) == 16
    assert reference.head_width(R110, 6) == 256
    step0 = flops.client_step_flops(R110, 0, 1)
    step6 = flops.client_step_flops(R110, 6, 1)
    head = lambda c: 2 * c * 10
    fc = 2 * 256 * 10
    # the two tiers run the same convolutions; they differ in the head, in
    # which layer reads z without an input gradient, and in the classifier
    stem = 2 * 32 * 32 * 9 * 3 * 16
    blocks = [flops.block_flops(b, hw) for b, hw in zip(
        reference.block_plan(R110), [32] * 12 + [32] + [16] * 11 + [16] + [8] * 11)]
    conv = 2 * stem + sum(3 * f for f, _, _ in blocks)
    assert step0 == conv - blocks[0][1] + 3 * head(16) + 3 * fc
    assert step6 == conv + 3 * head(256) + 2 * fc


def test_forward_is_a_third_of_a_full_step_without_heads():
    # one forward of the whole model counts each layer once
    fwd = flops.forward_flops(R110, 1)
    assert fwd > 0
    assert abs(3 * fwd - flops.client_step_flops(R110, 6, 1)) < 0.02 * 3 * fwd
