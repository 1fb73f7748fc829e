"""FLOPs of the MNIST zoo CNN from its layer shapes, added the way a later
PR adds a model family's arithmetic — this file, named by the
configuration's ``flops.function``: two VALID 3x3 convolutions (1 -> 32 on
26x26, 32 -> 64 on 24x24) and a dense head on the 12x12x64 pooled map."""


def forward_macs(num_classes=10):
    return 26 * 26 * 9 * 1 * 32 + 24 * 24 * 9 * 32 * 64 + 12 * 12 * 64 * num_classes


def per_record(spec, traffic):
    return {"train": 6.0 * forward_macs(spec["num_classes"])}
