"""Logical device mesh construction.

The mesh is the TPU build's "cluster topology": where the reference
enumerates PS pods and worker pods (``k8s_instance_manager.py``), we
enumerate devices into named logical axes:

- ``dp``   data parallel (gradient psum rides here)
- ``fsdp`` fully-sharded data parallel (parameter sharding)
- ``tp``   tensor parallel
- ``sp``   sequence/context parallel (ring attention)
- ``ep``   expert/embedding parallel (sharded embedding tables, MoE)
- ``pp``   pipeline parallel (GPipe stage schedule, ops/pipeline.py)

``--mesh_shape dp=4,tp=2`` on the CLI maps to ``MeshConfig``.  Axes of
size 1 are kept in the mesh (they cost nothing and keep PartitionSpecs
uniform), so the same model code runs on any mesh shape.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import Mesh

from elasticdl_tpu.utils.constants import MeshAxis
from elasticdl_tpu.utils.log_utils import default_logger as logger


def parse_mesh_shape(mesh_shape: str) -> dict[str, int]:
    """Parse ``'dp=4,tp=2'`` into an ordered axis-size dict."""
    out: dict[str, int] = {}
    if not mesh_shape:
        return out
    for part in mesh_shape.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, size = part.partition("=")
        name = name.strip()
        if name not in MeshAxis.ALL:
            raise ValueError(
                f"unknown mesh axis {name!r}; valid: {MeshAxis.ALL}"
            )
        out[name] = int(size)
        if out[name] <= 0:
            raise ValueError(f"axis {name!r} must be positive")
    return out


def detect_num_slices(devices, slice_index_fn=None) -> int:
    """Distinct TPU slices among ``devices`` (1 when the backend exposes
    no ``slice_index`` — CPU, or a single slice).

    ``slice_index_fn`` overrides the attribute lookup — how the
    multichip dryrun forces a multi-slice layout onto host-platform CPU
    devices (which cannot carry a ``slice_index``)."""
    if slice_index_fn is not None:
        return len({slice_index_fn(d) for d in devices}) or 1
    slices = {getattr(d, "slice_index", None) for d in devices}
    if None in slices or not slices:
        return 1
    return len(slices)


def slice_assignments(num_processes: int, num_slices: int) -> list[int]:
    """THE canonical process->slice map: contiguous blocks, earlier
    slices absorbing the remainder (``np.array_split`` semantics).

    Shared by the instance manager (world kwargs), the lockstep worker
    (forced slice layout on backends without a device ``slice_index``)
    and the replica ring (off-slice neighbor repin), so no two layers
    can ever disagree about which process lives on which slice."""
    if num_processes <= 0:
        return []
    num_slices = max(1, min(int(num_slices), num_processes))
    out: list[int] = []
    base, extra = divmod(num_processes, num_slices)
    for s in range(num_slices):
        out.extend([s] * (base + (1 if s < extra else 0)))
    return out


def process_slice_index_fn(num_processes: int, num_slices: int):
    """A ``slice_index_fn`` for :meth:`MeshConfig.create` deriving each
    device's slice from its owning PROCESS via the canonical
    :func:`slice_assignments` map — how a forced multi-slice layout is
    imposed on backends whose devices carry no usable ``slice_index``.
    Deliberately ignores any device ``slice_index``: multi-process CPU
    worlds expose a constant 0 on EVERY device, which would collapse
    the forced layout back to one slice; callers that trust hardware
    attributes go through :func:`resolved_slice_index_fn`."""
    assign = slice_assignments(num_processes, num_slices)

    def fn(device):
        proc = int(getattr(device, "process_index", 0) or 0)
        return assign[min(proc, len(assign) - 1)] if assign else 0

    return fn


def mesh_process_slice_map(mesh, slice_index_fn=None) -> list[int]:
    """process_index -> slice id for every process in the mesh, derived
    from the DEVICES (the resolved layout the collectives actually
    follow), ordered by process index.  On hardware whose ``slice_index``
    disagrees with the canonical process->slice assignment, the mesh is
    the truth — consumers that need physical placement (the replica
    ring's off-slice guarantee) read this, never the canonical map."""
    get_slice = slice_index_fn or (
        lambda d: getattr(d, "slice_index", 0) or 0
    )
    by_proc: dict[int, int] = {}
    for d in mesh.devices.flat:
        by_proc[int(d.process_index)] = int(get_slice(d))
    return [by_proc[p] for p in sorted(by_proc)]


def resolved_slice_index_fn(devices, num_processes: int, num_slices: int):
    """The ``slice_index_fn`` a world assigned ``num_slices`` slices
    should build its mesh with:

    - None when single-slice, or when the backend already exposes a
      non-degenerate multi-slice topology (real TPU multislice: the
      hardware ``slice_index`` is authoritative);
    - the canonical process->slice map otherwise (CPU backends expose
      no ``slice_index`` — or a constant one on every device of a
      multi-process world, which is just as sliceless)."""
    if num_slices <= 1:
        return None
    if detect_num_slices(devices) > 1:
        return None
    return process_slice_index_fn(num_processes, num_slices)


def plan_dcn_axes(
    sizes: dict[str, int], n_slices: int, dcn_axes: dict[str, int] | None
) -> dict[str, int]:
    """Which part of each mesh axis spans slices (rides DCN).

    Defaults to putting ALL of the slice dimension on ``dp`` — gradient
    all-reduce is the lowest-rate collective, so it is the one that can
    afford DCN; everything else stays intra-slice on ICI (the
    scaling-book layout).  An explicit ``dcn_axes`` (from
    ``--dcn_mesh_shape``) overrides, e.g. ``fsdp=2`` for cross-slice
    parameter sharding.
    """
    if n_slices <= 1:
        return {}
    if dcn_axes:
        prod = int(np.prod(list(dcn_axes.values())))
        if prod != n_slices:
            raise ValueError(
                f"dcn_mesh_shape product {prod} != number of slices "
                f"{n_slices}"
            )
        for axis, deg in dcn_axes.items():
            if sizes.get(axis, 1) % deg:
                raise ValueError(
                    f"dcn axis {axis}={deg} does not divide mesh "
                    f"{axis}={sizes.get(axis, 1)}"
                )
        return dict(dcn_axes)
    if sizes.get(MeshAxis.DP, 1) % n_slices:
        raise ValueError(
            f"dp={sizes.get(MeshAxis.DP, 1)} not divisible by "
            f"{n_slices} slices; pass --dcn_mesh_shape explicitly"
        )
    return {MeshAxis.DP: n_slices}


def order_devices_hybrid(
    devices, sizes: dict[str, int], dcn: dict[str, int], slice_index_fn=None
) -> np.ndarray:
    """Hybrid ordering for devices WITHOUT a hardware topology (host
    platform, forced slices): group devices by slice, lay each slice
    out row-major over the intra-slice (ICI) shape, and concatenate
    slices along the DCN axes — so the outer (slice) stride of a DCN axis
    crosses slices and everything else stays inside one.

    (``mesh_utils.create_hybrid_device_mesh`` does this with
    topology-aware intra-slice orders on real multislice hardware; this
    keeps the same slice/axis assignment.)
    """
    get_slice = slice_index_fn or (
        lambda d: getattr(d, "slice_index", 0)
    )
    by_slice: dict = {}
    for d in devices:
        by_slice.setdefault(get_slice(d), []).append(d)
    slice_ids = sorted(by_slice)
    if len({len(v) for v in by_slice.values()}) != 1:
        raise ValueError(f"unequal devices per slice: {sorted(by_slice)}")
    live = [a for a, deg in dcn.items() if deg > 1]
    if len(live) != 1:
        raise ValueError(
            "row-major hybrid ordering supports exactly one DCN axis; "
            f"got {dcn}"
        )
    ici_shape = tuple(sizes[a] // dcn.get(a, 1) for a in sizes)
    arrays = [
        np.asarray(by_slice[s], dtype=object).reshape(ici_shape)
        for s in slice_ids
    ]
    # slice-major concatenation along the DCN axis: positions that differ
    # only in their intra-slice coordinate stay within one slice
    return np.concatenate(arrays, axis=list(sizes).index(live[0]))


@dataclass
class MeshConfig:
    """Axis sizes for the logical mesh; unspecified axes default to 1.

    When ``dp`` is omitted it is *inferred* as "all remaining devices"
    (num_devices / product of the given axes), so a bare job scales to
    whatever slice it lands on.  ``dcn_axes`` declares which part of
    which axis spans TPU slices (multi-slice jobs; collectives on those
    axis strides ride DCN, everything else ICI).
    """

    axes: dict[str, int] = field(default_factory=dict)
    dcn_axes: dict[str, int] = field(default_factory=dict)

    @classmethod
    def from_string(
        cls, mesh_shape: str, dcn_mesh_shape: str = ""
    ) -> "MeshConfig":
        return cls(
            parse_mesh_shape(mesh_shape), parse_mesh_shape(dcn_mesh_shape)
        )

    def resolved_axes(self, num_devices: int) -> dict[str, int]:
        sizes = {name: self.axes.get(name, 1) for name in MeshAxis.ALL}
        fixed = int(np.prod([s for s in sizes.values()]))
        if MeshAxis.DP not in self.axes:
            if num_devices % (fixed) != 0:
                raise ValueError(
                    f"{num_devices} devices not divisible by mesh "
                    f"product {fixed}"
                )
            sizes[MeshAxis.DP] = num_devices // fixed
        total = int(np.prod(list(sizes.values())))
        if total > num_devices:
            raise ValueError(
                f"mesh {sizes} needs {total} devices but "
                f"{num_devices} are available"
            )
        return sizes

    def create(self, devices=None, slice_index_fn=None) -> Mesh:
        """``slice_index_fn``: override the per-device slice attribute —
        the dryrun's hook for exercising the hybrid ICI/DCN layout on
        host-platform CPU devices (``__graft_entry__.dryrun_multichip``
        forces 2 slices through plan_dcn_axes with it)."""
        devices = devices if devices is not None else jax.devices()
        sizes = self.resolved_axes(len(devices))
        total = int(np.prod(list(sizes.values())))
        # an explicitly smaller mesh uses a device subset (useful for
        # single-device baselines on a multi-device host)
        devices = list(devices)[:total]
        platform = devices[0].platform
        axis_names = tuple(sizes)
        shape = tuple(sizes[a] for a in axis_names)
        get_slice = slice_index_fn or (
            lambda d: getattr(d, "slice_index", 0)
        )
        n_slices = detect_num_slices(devices, slice_index_fn)
        if n_slices > 1:
            per_slice: dict = {}
            for d in devices:
                key = get_slice(d)
                per_slice[key] = per_slice.get(key, 0) + 1
            if len(set(per_slice.values())) != 1:
                # a sub-mesh that doesn't tile the slices evenly (e.g. an
                # explicit smaller mesh truncated mid-slice) cannot be
                # laid out hybrid; a flat mesh is still correct
                logger.warning(
                    "Device subset spans slices unevenly (%s); building "
                    "a flat mesh instead of a hybrid one",
                    per_slice,
                )
                n_slices = 1
        if n_slices > 1:
            dcn = plan_dcn_axes(sizes, n_slices, self.dcn_axes or None)
            ici_shape = tuple(
                sizes[a] // dcn.get(a, 1) for a in axis_names
            )
            dcn_shape = tuple(dcn.get(a, 1) for a in axis_names)
            if slice_index_fn is not None or platform == "cpu":
                # forced slices (mesh_utils would re-read the absent
                # device attributes) and host-platform devices (no
                # topology to exploit): the in-repo hybrid ordering
                device_array = order_devices_hybrid(
                    devices, sizes, dcn, slice_index_fn
                )
            else:
                # real multislice hardware: a layout mesh_utils refuses
                # is an error to surface, not to paper over row-major
                from jax.experimental import mesh_utils

                device_array = mesh_utils.create_hybrid_device_mesh(
                    ici_shape, dcn_shape, devices=devices
                )
            topology = f"{n_slices} slices (DCN axes {dcn})"
        else:
            if self.dcn_axes:
                # not silently: the user declared a multi-slice layout the
                # backend doesn't expose — collectives may cross DCN in
                # whatever order the flat mesh happens to pick
                logger.warning(
                    "--dcn_mesh_shape %s given but the backend exposes "
                    "a single slice (no device slice_index); building a "
                    "flat mesh",
                    self.dcn_axes,
                )
            if platform == "cpu":
                # host-platform devices have no interconnect topology:
                # row-major is the layout
                device_array = np.asarray(devices).reshape(shape)
            else:
                # topology-aware order; on a chip a refused layout is
                # an error to surface, never a silent row-major mesh
                from jax.experimental import mesh_utils

                device_array = mesh_utils.create_device_mesh(
                    shape, devices=devices
                )
            topology = "1 slice"
        mesh = Mesh(device_array, axis_names)
        logger.info(
            "Created mesh %s over %d %s devices (%s), %s",
            {a: s for a, s in sizes.items() if s > 1} or {"dp": 1},
            len(devices),
            platform,
            devices[0].device_kind,
            topology,
        )
        return mesh


def data_parallel_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes the batch dimension is sharded over, size-1 axes excluded (dp
    and fsdp both consume batch; fsdp additionally shards parameters).
    The single definition of "the batch axes" — batch_sharding and
    batch_divisor both derive from it."""
    return tuple(
        a
        for a in (MeshAxis.DP, MeshAxis.FSDP)
        if a in mesh.axis_names and mesh.shape[a] > 1
    )


def batch_divisor(mesh: Mesh) -> int:
    """Global batch must be divisible by this for input sharding."""
    n = 1
    for a in data_parallel_axes(mesh):
        n *= mesh.shape[a]
    return n
