"""Model FLOPs of ResNet-50, from the layer shapes.  Training counts the
forward pass once and the backward pass twice (3x forward); one
multiply-accumulate is 2 FLOPs."""

from __future__ import annotations


def forward_macs(
    image_size: int = 224,
    num_classes: int = 1000,
    stride_on_first_1x1: bool = True,
) -> float:
    """Multiply-accumulates of one image through ResNet-50 (He et al.,
    Table 1), from the layer shapes: 7x7/2 stem, 3x3/2 max pool, four
    stages of [3, 4, 6, 3] bottlenecks (1x1, 3x3, 1x1) with a projection
    shortcut on each stage's first block, global pool, dense head.
    ``stride_on_first_1x1`` is the original placement (the zoo's
    ``ConvBlock``); the v1.5 variant strides the 3x3."""

    def conv(hw, kernel, cin, cout):
        return hw * hw * kernel * kernel * cin * cout

    hw = image_size // 2
    macs = conv(hw, 7, 3, 64)
    hw //= 2  # max pool
    cin = 64
    for (f1, f2, f3), blocks, stride in (
        ((64, 64, 256), 3, 1),
        ((128, 128, 512), 4, 2),
        ((256, 256, 1024), 6, 2),
        ((512, 512, 2048), 3, 2),
    ):
        for block in range(blocks):
            s = stride if block == 0 else 1
            out_hw = hw // s
            hw_a = out_hw if stride_on_first_1x1 else hw
            macs += conv(hw_a, 1, cin, f1)
            macs += conv(out_hw, 3, f1, f2)
            macs += conv(out_hw, 1, f2, f3)
            if block == 0:
                macs += conv(out_hw, 1, cin, f3)
            cin, hw = f3, out_hw
    return float(macs + cin * num_classes)


def per_record(spec: dict, traffic: dict) -> dict:
    """A record is one image."""
    return {
        "train": 6.0 * forward_macs(spec["image_size"], spec["num_classes"])
    }
