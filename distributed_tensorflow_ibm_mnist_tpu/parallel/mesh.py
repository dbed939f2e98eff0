"""Device-mesh construction and the package's one shard_map call site.

The reference's cluster topology was a ClusterSpec built from role flags
(SURVEY.md §1 L2).  The TPU-native analog is a named ``jax.sharding.Mesh``
over the visible devices; parallelism strategies are just mesh axes:
``data`` (DP), ``model`` (TP), ``seq`` (SP/ring).  Multi-host bootstrap
(``jax.distributed.initialize``) lives in ``launch/tpu_vm.py``.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off.

    Replicated outputs produced via psum are correct but the checker can't
    always prove it, so it is disabled at this single call site.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def hybrid_mesh_shapes(
    dp: int, tp: int, sp: int, pp: int, dcn_dp: int
) -> tuple[tuple[int, int, int, int], tuple[int, int, int, int]]:
    """Split a (data, model, seq, pipe) request into the per-slice (ICI)
    and cross-slice (DCN) factor shapes ``create_hybrid_device_mesh``
    expects: only the data axis spans slices (gradient all-reduce is the
    one per-step collective that tolerates DCN latency; model/seq/pipe
    collectives stay on intra-slice ICI), so dcn_dp must divide dp."""
    if dcn_dp < 1:
        raise ValueError(f"dcn_dp must be >= 1, got {dcn_dp}")
    if dp % dcn_dp:
        raise ValueError(
            f"dcn_dp ({dcn_dp}) must divide dp ({dp}): the data axis factors "
            "as (cross-slice x within-slice)"
        )
    return (dp // dcn_dp, tp, sp, pp), (dcn_dp, 1, 1, 1)


def pick_multislice_devices(devices: list, dcn_dp: int, per_slice: int) -> list:
    """Select ``per_slice`` devices from EACH of ``dcn_dp`` TPU slices.

    The multislice device-selection half of ``make_mesh(dcn_dp > 1)``,
    factored pure (VERDICT.md r3 item 6: the positive branch was covered
    only by refusal tests) so it runs in CI against mock devices carrying
    ``slice_index``.  A flat ``devices[:need]`` prefix would grab slice
    0's chips first and conclude "one slice"; this groups by
    ``slice_index`` (None — non-multislice runtimes — never counts),
    requires ``dcn_dp`` slices with at least ``per_slice`` devices each,
    and returns slice-major, slice-contiguous devices — the order
    ``create_hybrid_device_mesh`` expects so only the leading (DCN) mesh
    factor crosses slices.
    """
    groups: dict = {}
    for d in devices:
        groups.setdefault(getattr(d, "slice_index", None), []).append(d)
    usable = sorted(
        s for s, g in groups.items() if s is not None and len(g) >= per_slice
    )
    if len(usable) < dcn_dp:
        found = sorted(s for s in groups if s is not None)
        raise ValueError(
            f"dcn_dp={dcn_dp} needs {dcn_dp} TPU slices with >= "
            f"{per_slice} devices each (found slice indices "
            f"{found or 'none'}); multislice runs come from the TPU "
            "runtime, not this host"
        )
    return [d for s in usable[:dcn_dp] for d in groups[s][:per_slice]]


def make_mesh(
    dp: int | None = None,
    tp: int = 1,
    sp: int = 1,
    pp: int = 1,
    devices: list | None = None,
    dcn_dp: int = 1,
) -> Mesh:
    """Build a ``(data, model, seq, pipe)`` mesh over the visible devices.

    ``dp=None`` uses all remaining devices for data parallelism.  Axis sizes
    must multiply to at most ``len(devices)``; trailing devices are unused.
    Axis order puts ``data`` outermost (DCN-friendly across slices) and the
    compute-coupled axes (``model``/``seq``/``pipe``) innermost so their
    collectives ride adjacent ICI links.

    ``dcn_dp > 1`` is the MULTISLICE form: the devices span that many TPU
    slices (each device carries a ``slice_index``), the data axis factors
    as (dcn_dp slices x dp/dcn_dp within each slice), and
    ``mesh_utils.create_hybrid_device_mesh`` lays devices out so only the
    data axis's gradient all-reduce crosses DCN — model/seq/pipe
    collectives never leave a slice's ICI.  This is the reference's
    multi-worker scaling story (SURVEY.md §2.4: PS/NCCL across IBM-Cloud
    workers) in TPU-native form; single-slice environments (this sandbox,
    the virtual CPU mesh) refuse it with a clear error rather than
    silently degrading to a flat mesh.
    """
    if dcn_dp < 1:
        raise ValueError(f"dcn_dp must be >= 1, got {dcn_dp}")
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if dp is None or dp == 0:
        dp = n // (tp * sp * pp)
        if dp == 0:
            raise ValueError(
                f"tp*sp*pp={tp * sp * pp} exceeds device count {n}; no room for a data axis"
            )
    need = dp * tp * sp * pp
    if need > n:
        raise ValueError(f"mesh ({dp}x{tp}x{sp}x{pp}) needs {need} devices, have {n}")
    if dcn_dp > 1:
        ici_shape, dcn_shape = hybrid_mesh_shapes(dp, tp, sp, pp, dcn_dp)
        chosen = pick_multislice_devices(devices, dcn_dp, need // dcn_dp)
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=chosen
        )
        return Mesh(arr, ("data", "model", "seq", "pipe"))
    arr = _device_grid((dp, tp, sp, pp), devices[:need])
    return Mesh(arr, ("data", "model", "seq", "pipe"))


def _device_grid(shape: tuple[int, ...], devices: list) -> np.ndarray:
    """Arrange devices into the mesh grid, physical topology permitting.

    On real TPU slices ``mesh_utils.create_device_mesh`` maps logical axes
    onto the physical torus so each axis's collectives ride contiguous ICI
    rings — list-order reshape (what round 1 did; VERDICT.md item 7) gives
    inner axes non-neighbor links.  Virtual/CPU devices carry no coords
    (topology is meaningless there), and create_device_mesh rejects a
    strict subset of the visible chips, so those two cases — and only
    those — take the list-order reshape.  A create_device_mesh failure on
    the full set of real chips is raised: a silent fall to list order
    would change which links each axis rides.
    """
    first = devices[0]
    on_tpu = getattr(first, "platform", "") == "tpu" and hasattr(first, "coords")
    if on_tpu and len(devices) == len(jax.devices()) and len(devices) > 1:
        from jax.experimental import mesh_utils

        return mesh_utils.create_device_mesh(shape, devices=devices)
    return np.array(devices).reshape(shape)
