"""The `--steps_per_dispatch auto` sizing rule (trainer/stacking.py)."""

import numpy as np
import pytest

from elasticdl_tpu.trainer import stacking


def test_auto_k_pins_the_sizing_rule():
    """The rule that replaced the r3 hand-tuned constants: a 7MB put
    target sizes the dispatch group, so under a 130ms dispatch overhead
    803KB f32 mnist batches get k=9 and the 205KB uint8 wire gets k=36.
    Tiny deepfm batches cap at MAX_AUTO_K; cheap-dispatch hosts get k=1
    (no stacking needed)."""
    mnist_bytes = 256 * 28 * 28 * 4 + 256 * 4  # f32 images + i32 labels
    assert stacking.auto_steps_per_dispatch(mnist_bytes, 0.13) == 9
    mnist_u8 = 256 * 28 * 28 + 256 * 4  # uint8 wire (device_parse)
    assert stacking.auto_steps_per_dispatch(mnist_u8, 0.13) == 36
    deepfm_bytes = 4096 * 10 * 2 + 4096 * 4  # int16 wire ids
    assert (
        stacking.auto_steps_per_dispatch(deepfm_bytes, 0.13)
        == stacking.MAX_AUTO_K
    )
    # cheap dispatch (local PCIe): stacking buys nothing, keep hooks
    # per-step
    assert stacking.auto_steps_per_dispatch(mnist_bytes, 0.0005) == 1
    # degenerate inputs
    assert stacking.auto_steps_per_dispatch(0, 0.13) == 1
    # a batch bigger than the cliff still dispatches (k=1)
    assert (
        stacking.auto_steps_per_dispatch(
            stacking.TRANSFER_CLIFF_BYTES * 2, 0.13
        )
        == 1
    )


def test_choose_stack_k_shared_rule():
    """THE stack_k selection rule the three runtimes share: stacking
    only in training and only for k>1; 'auto' passes through except in
    lockstep worlds (allow_auto=False — a per-process auto probe could
    deadlock the collectives)."""
    assert stacking.choose_stack_k(4, training=True) == 4
    assert stacking.choose_stack_k("auto", training=True) == "auto"
    assert stacking.choose_stack_k("auto", True, allow_auto=False) is None
    assert stacking.choose_stack_k(4, training=False) is None
    assert stacking.choose_stack_k(1, training=True) is None
    assert stacking.choose_stack_k(None, training=True) is None
    assert stacking.choose_stack_k(0, training=True) is None


def test_resolve_explicit_k_passthrough():
    assert stacking.resolve_steps_per_dispatch(4) == 4
    assert stacking.resolve_steps_per_dispatch(None) == 1
    assert stacking.resolve_steps_per_dispatch(0) == 1


def test_resolve_auto_uses_batch_bytes(monkeypatch):
    monkeypatch.setattr(stacking, "_DISPATCH_OVERHEAD", [0.13])
    feats = {"image": np.zeros((256, 28, 28), np.float32)}
    labels = np.zeros(256, np.int32)
    assert stacking.resolve_steps_per_dispatch(
        "auto", (feats, labels)
    ) == 9
    # cheap link -> 1
    monkeypatch.setattr(stacking, "_DISPATCH_OVERHEAD", [0.0001])
    assert (
        stacking.resolve_steps_per_dispatch("auto", (feats, labels)) == 1
    )


def test_run_stacked_steps_resolves_auto(monkeypatch):
    """'auto' flows through the grouping loop: with a fake expensive
    link the first batch's bytes pick the group size."""
    monkeypatch.setattr(stacking, "_DISPATCH_OVERHEAD", [0.13])

    class FakeTrainer:
        def __init__(self):
            self.stacked_calls = []
            self.single_calls = 0

        def pad_to(self, tree, rows):
            return tree

        def row_mask(self, n_real, rows):
            return np.ones(rows, np.float32)

        def place_batch(self, tree):
            return tree

        def place_stacked(self, tree):
            return tree

        def train_step(self, f, l, mask):
            self.single_calls += 1

        def train_steps_stacked(self, f, l, weights):
            self.stacked_calls.append(weights.shape[0])

    # ~1.05MB batches (f32 features + f64 labels) -> auto k = 6
    batch = ({"x": np.zeros((256, 1024), np.float32)}, np.zeros(256))
    batches = [batch] * 26
    trainer = FakeTrainer()
    n = stacking.run_stacked_steps(
        lambda: trainer, iter(batches), "auto", canonical_rows=256
    )
    assert n == 26 * 256
    # four full groups; the 2-batch leftover runs as single steps, never
    # a third scan length
    assert trainer.stacked_calls == [6, 6, 6, 6]
    assert trainer.single_calls == 2


def test_run_stacked_steps_requires_canonical_rows():
    """There is one grouping policy: a caller that omits the canonical
    row count is refused at the call, not given another program."""
    with pytest.raises(TypeError, match="canonical_rows"):
        stacking.run_stacked_steps(lambda: None, iter([]), 1)
