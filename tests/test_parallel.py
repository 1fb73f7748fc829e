"""Mesh / sharding / SPMD trainer tests on the virtual 8-device CPU mesh
(SURVEY §4: collapse the pod slice, keep the sharding real)."""

import jax
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.models import mnist_functional_api as mnist
from elasticdl_tpu.parallel.distributed import SPMDTrainer
from elasticdl_tpu.parallel.mesh import MeshConfig, batch_divisor, parse_mesh_shape
from elasticdl_tpu.parallel.sharding import (
    Rule,
    infer_param_specs,
    batch_sharding,
)


class _FakeDev:
    def __init__(self, i, slice_index):
        self.id = i
        self.slice_index = slice_index

    def __repr__(self):
        return f"d{self.id}s{self.slice_index}"


class TestMultiSlice:
    """Hybrid (multi-slice) mesh planning: the slice dimension lands on
    dp (DCN-tolerant gradient all-reduce) and everything else stays
    intra-slice on ICI."""

    def test_detect_and_default_plan(self):
        from elasticdl_tpu.parallel.mesh import (
            detect_num_slices,
            plan_dcn_axes,
        )

        devs = [_FakeDev(i, i // 4) for i in range(8)]
        assert detect_num_slices(devs) == 2
        sizes = {"dp": 4, "fsdp": 1, "tp": 2, "sp": 1, "ep": 1, "pp": 1}
        assert plan_dcn_axes(sizes, 2, None) == {"dp": 2}

    def test_plan_rejects_bad_shapes(self):
        from elasticdl_tpu.parallel.mesh import plan_dcn_axes

        sizes = {"dp": 3, "fsdp": 1, "tp": 1, "sp": 1, "ep": 1, "pp": 1}
        with pytest.raises(ValueError):
            plan_dcn_axes(sizes, 2, None)  # dp=3 not divisible by 2 slices
        with pytest.raises(ValueError):
            plan_dcn_axes(sizes, 2, {"dp": 3})  # product != slices

    def test_explicit_dcn_axes(self):
        from elasticdl_tpu.parallel.mesh import plan_dcn_axes

        sizes = {"dp": 2, "fsdp": 4, "tp": 1, "sp": 1, "ep": 1, "pp": 1}
        assert plan_dcn_axes(sizes, 4, {"fsdp": 4}) == {"fsdp": 4}

    def test_fallback_ordering_keeps_ici_axes_intra_slice(self):
        from elasticdl_tpu.parallel.mesh import order_devices_hybrid

        devs = [_FakeDev(i, i // 4) for i in range(8)]
        sizes = {"dp": 4, "fsdp": 1, "tp": 2, "sp": 1, "ep": 1, "pp": 1}
        arr = order_devices_hybrid(devs, sizes, {"dp": 2})
        assert arr.shape == (4, 1, 2, 1, 1, 1)
        # tp neighbors (last varying axis) never cross a slice
        for i in range(4):
            pair = arr[i, 0, :, 0, 0, 0]
            assert pair[0].slice_index == pair[1].slice_index
        # the dp axis crosses slices exactly at its halfway stride
        dp_slices = [arr[i, 0, 0, 0, 0, 0].slice_index for i in range(4)]
        assert dp_slices == [0, 0, 1, 1]

    def test_single_slice_create_unchanged(self):
        mesh = MeshConfig.from_string("dp=4,tp=2").create()
        assert dict(mesh.shape) == {
            "dp": 4, "fsdp": 1, "tp": 2, "sp": 1, "ep": 1, "pp": 1
        }


class TestMeshConfig:
    def test_parse(self):
        assert parse_mesh_shape("dp=4,tp=2") == {"dp": 4, "tp": 2}
        assert parse_mesh_shape("") == {}
        with pytest.raises(ValueError):
            parse_mesh_shape("zz=2")
        with pytest.raises(ValueError):
            parse_mesh_shape("dp=0")

    def test_default_all_dp(self):
        mesh = MeshConfig.from_string("").create()
        assert mesh.shape["dp"] == 8
        assert mesh.shape["tp"] == 1

    def test_mixed_axes(self):
        mesh = MeshConfig.from_string("dp=2,tp=2,sp=2").create()
        assert mesh.shape["dp"] == 2
        assert mesh.shape["tp"] == 2
        assert mesh.shape["sp"] == 2
        assert batch_divisor(mesh) == 2

    def test_dp_inferred_from_remaining(self):
        mesh = MeshConfig.from_string("tp=2").create()
        assert mesh.shape["dp"] == 4

    def test_bad_product_raises(self):
        with pytest.raises(ValueError):
            MeshConfig.from_string("dp=16").create()  # more than 8 devices
        with pytest.raises(ValueError):
            MeshConfig.from_string("tp=3").create()  # 8 % 3 != 0

    def test_explicit_subset_mesh(self):
        mesh = MeshConfig.from_string("dp=3").create()
        assert mesh.shape["dp"] == 3 and len(mesh.devices.flatten()) == 3


class TestShardingRules:
    def _mesh(self, shape):
        return MeshConfig.from_string(shape).create()

    def test_rules_first_match_wins(self):
        mesh = self._mesh("dp=4,tp=2")
        params = {
            "attention": {"query": {"kernel": np.zeros((16, 8))}},
            "mlp": {"down": {"kernel": np.zeros((8, 16))}},
            "bias": np.zeros((7,)),
        }
        from elasticdl_tpu.parallel.sharding import default_tp_rules

        specs = infer_param_specs(params, mesh, default_tp_rules())
        assert specs["attention"]["query"]["kernel"] == P(None, "tp")
        assert specs["mlp"]["down"]["kernel"] == P("tp", None)
        assert specs["bias"] == P()  # 7 not divisible, no rule

    def test_rule_that_does_not_fit_falls_back(self):
        mesh = self._mesh("dp=4,tp=2")
        specs = infer_param_specs(
            {"q": {"kernel": np.zeros((16, 7))}},  # 7 % 2 != 0
            mesh,
            [Rule(r"q/kernel$", P(None, "tp"))],
        )
        assert specs["q"]["kernel"] == P()

    def test_fsdp_auto_sharding(self):
        mesh = self._mesh("fsdp=8")
        specs = infer_param_specs(
            {"w": np.zeros((24, 33)), "tiny": np.zeros((3,))}, mesh
        )
        assert specs["w"] == P("fsdp", None)
        assert specs["tiny"] == P()

    def test_batch_sharding_spans_dp_and_fsdp(self):
        mesh = self._mesh("dp=2,fsdp=4")
        sh = batch_sharding(mesh, ndim=2)
        assert sh.spec == P(("dp", "fsdp"), None)
        assert batch_divisor(mesh) == 8


def _make_batch(n=64):
    rng = np.random.RandomState(0)
    feats = {"image": rng.rand(n, 28, 28).astype(np.float32)}
    labels = rng.randint(0, 10, size=n).astype(np.int32)
    return feats, labels


class TestSPMDTrainer:
    def _trainer(self, mesh_shape, **kw):
        mesh = MeshConfig.from_string(mesh_shape).create()
        feats, _ = _make_batch(8)
        return SPMDTrainer(
            mesh,
            mnist.custom_model(),
            mnist.loss,
            optax.sgd(0.01),
            feats,
            **kw,
        )

    def test_dp_step_runs_and_updates(self):
        tr = self._trainer("dp=8")
        feats, labels = _make_batch(64)
        losses = [
            float(
                tr.train_step(
                    tr.place_batch(feats), tr.place_batch(labels)
                )["loss"]
            )
            for _ in range(24)
        ]
        assert tr.step == 24
        # memorizing one fixed batch: loss must drop substantially (noisy
        # early steps allowed — dropout is live in training mode)
        assert min(losses[-4:]) < losses[0] * 0.5, losses

    def test_dp_matches_single_device_training(self):
        """DP over 8 devices must produce the same math as one device
        (the reference's quality bar 'PS-trained ≈ local-trained',
        worker_ps_interaction_test.py)."""
        feats, labels = _make_batch(64)
        tr8 = self._trainer("dp=8")
        losses8 = [
            float(
                tr8.train_step(
                    tr8.place_batch(feats), tr8.place_batch(labels)
                )["loss"]
            )
            for _ in range(3)
        ]
        tr1 = self._trainer("dp=1")
        losses1 = [
            float(
                tr1.train_step(
                    tr1.place_batch(feats), tr1.place_batch(labels)
                )["loss"]
            )
            for _ in range(3)
        ]
        np.testing.assert_allclose(losses8, losses1, rtol=2e-4)

    def test_fsdp_state_is_sharded(self):
        tr = self._trainer("fsdp=8")
        # at least one parameter leaf must actually be sharded over fsdp
        sharded = [
            leaf.sharding.spec
            for leaf in jax.tree_util.tree_leaves(tr.state.params)
            if any(s is not None for s in leaf.sharding.spec)
        ]
        assert sharded, "no parameter was fsdp-sharded"
        feats, labels = _make_batch(32)
        m = tr.train_step(tr.place_batch(feats), tr.place_batch(labels))
        assert np.isfinite(float(m["loss"]))

    def test_fsdp_matches_dp_training(self):
        feats, labels = _make_batch(64)
        tr_dp = self._trainer("dp=8")
        tr_fsdp = self._trainer("fsdp=8")
        for _ in range(2):
            ld = tr_dp.train_step(
                tr_dp.place_batch(feats), tr_dp.place_batch(labels)
            )
            lf = tr_fsdp.train_step(
                tr_fsdp.place_batch(feats), tr_fsdp.place_batch(labels)
            )
        np.testing.assert_allclose(
            float(ld["loss"]), float(lf["loss"]), rtol=2e-4
        )

    def test_eval_and_predict_steps(self):
        tr = self._trainer("dp=8")
        feats, labels = _make_batch(16)
        outputs, loss = tr.eval_step(
            tr.place_batch(feats), tr.place_batch(labels)
        )
        assert np.asarray(outputs).shape == (16, 10)
        assert np.isfinite(float(loss))
        preds = tr.predict_step(tr.place_batch(feats))
        assert np.asarray(preds).shape == (16, 10)

