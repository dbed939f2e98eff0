"""Mesh construction: axis layout + topology-aware device placement."""

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_ibm_mnist_tpu.parallel import mesh as mesh_mod
from distributed_tensorflow_ibm_mnist_tpu.parallel.mesh import make_mesh


pytestmark = pytest.mark.quick  # core numerics: part of the -m quick signal loop


def test_mesh_axes_and_sizes(eight_devices):
    m = make_mesh(dp=2, tp=2, sp=2)
    assert m.axis_names == ("data", "model", "seq", "pipe")
    assert m.shape["data"] == 2 and m.shape["model"] == 2 and m.shape["seq"] == 2
    assert m.shape["pipe"] == 1


def test_mesh_dp_fills_remaining(eight_devices):
    m = make_mesh(tp=2)
    assert m.shape["data"] == 4


def test_mesh_oversubscription_raises(eight_devices):
    with pytest.raises(ValueError, match="needs 16 devices"):
        make_mesh(dp=4, tp=4)


def test_cpu_mesh_is_list_order(eight_devices):
    """Virtual CPU devices have no topology; placement must stay list-order
    (create_device_mesh would reject them anyway)."""
    m = make_mesh(dp=8)
    assert list(m.devices.flat) == eight_devices[:8]


def test_tpu_path_routes_through_create_device_mesh(monkeypatch):
    """On real TPU devices make_mesh must delegate to
    jax.experimental.mesh_utils.create_device_mesh (VERDICT.md round-1
    item 7: list-order reshape ignores the physical torus)."""

    class FakeTpu:
        platform = "tpu"

        def __init__(self, i):
            self.id = i
            self.coords = (i, 0, 0)

        def __repr__(self):
            return f"FakeTpu({self.id})"

    fakes = [FakeTpu(i) for i in range(8)]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: fakes)
    called = {}

    from jax.experimental import mesh_utils

    def fake_create(shape, devices=None):
        called["shape"] = tuple(shape)
        called["devices"] = list(devices)
        return np.array(devices, dtype=object).reshape(shape)

    monkeypatch.setattr(mesh_utils, "create_device_mesh", fake_create)
    grid = mesh_mod._device_grid((2, 2, 2, 1), fakes)
    assert called["shape"] == (2, 2, 2, 1)
    assert called["devices"] == fakes
    assert grid.shape == (2, 2, 2, 1)


def test_tpu_subset_falls_back_to_list_order(monkeypatch):
    """Using fewer devices than visible skips create_device_mesh (it requires
    the full slice) and keeps the plain reshape."""

    class FakeTpu:
        platform = "tpu"

        def __init__(self, i):
            self.id = i
            self.coords = (i, 0, 0)

    fakes = [FakeTpu(i) for i in range(8)]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: fakes)
    grid = mesh_mod._device_grid((4, 1, 1, 1), fakes[:4])
    assert [d.id for d in grid.flat] == [0, 1, 2, 3]


def test_hybrid_mesh_shapes():
    """Multislice factoring: only the data axis crosses DCN."""
    from distributed_tensorflow_ibm_mnist_tpu.parallel.mesh import hybrid_mesh_shapes

    assert hybrid_mesh_shapes(8, 2, 1, 1, dcn_dp=2) == ((4, 2, 1, 1), (2, 1, 1, 1))
    assert hybrid_mesh_shapes(4, 1, 1, 1, dcn_dp=4) == ((1, 1, 1, 1), (4, 1, 1, 1))
    with pytest.raises(ValueError, match="divide"):
        hybrid_mesh_shapes(6, 1, 1, 1, dcn_dp=4)
    with pytest.raises(ValueError, match=">= 1"):
        hybrid_mesh_shapes(4, 1, 1, 1, dcn_dp=0)


def test_dcn_dp_refused_without_multislice_devices(eight_devices):
    """Virtual CPU devices carry no slice_index: dcn_dp>1 must refuse with
    a clear error instead of silently building a flat mesh."""
    with pytest.raises(ValueError, match="slice"):
        make_mesh(dp=8, dcn_dp=2)


def test_config_dcn_dp_plumbs_to_mesh(eight_devices):
    """RunConfig.dcn_dp reaches make_mesh (and fails loudly here, where no
    multislice runtime exists) — even at dp=1, where the mesh build is
    otherwise skipped."""
    from distributed_tensorflow_ibm_mnist_tpu.core.trainer import Trainer
    from distributed_tensorflow_ibm_mnist_tpu.utils.config import RunConfig

    cfg = RunConfig(
        model="mlp", model_kwargs={"hidden": (32,)}, synthetic=True,
        n_train=64, n_test=32, batch_size=32, epochs=1, quiet=True,
        dp=8, dcn_dp=2,
    )
    with pytest.raises(ValueError, match="slice"):
        Trainer(cfg)
    # dp=1 must not silently ignore the multislice request...
    with pytest.raises(ValueError, match="divide"):
        Trainer(cfg.replace(dp=1))
    # ...and invalid values are refused, not clamped
    with pytest.raises(ValueError, match=">= 1"):
        Trainer(cfg.replace(dcn_dp=0))


class _SliceDev:
    """A real (virtual CPU) device dressed with a slice_index — enough for
    the multislice selection AND create_hybrid_device_mesh to run in CI."""

    def __init__(self, dev, slice_index):
        self._dev = dev
        self.slice_index = slice_index

    def __getattr__(self, name):
        return getattr(self._dev, name)

    def __repr__(self):
        return f"SliceDev({self._dev.id}, slice={self.slice_index})"


def test_pick_multislice_devices_groups_per_slice(eight_devices):
    """The positive multislice branch EXECUTES (VERDICT.md r3 item 6): the
    selection takes per_slice devices from each slice — never a flat
    prefix — ignores sliceless devices, and keeps slices contiguous."""
    from distributed_tensorflow_ibm_mnist_tpu.parallel.mesh import (
        pick_multislice_devices,
    )

    devs = list(eight_devices)
    # interleave slice membership so a flat prefix would be WRONG: slices
    # 0/1 alternate, plus two devices with no slice at the front
    mocked = [_SliceDev(d, i % 2) for i, d in enumerate(devs[2:])] + devs[:2]
    chosen = pick_multislice_devices(mocked, dcn_dp=2, per_slice=3)
    assert [c.slice_index for c in chosen] == [0, 0, 0, 1, 1, 1]
    assert len({c.id for c in chosen}) == 6
    # slice 0 got the even-indexed tail devices, slice 1 the odd ones
    assert [c.id for c in chosen[:3]] == [d.id for d in devs[2::2]]
    assert [c.id for c in chosen[3:]] == [d.id for d in devs[3::2]]

    # not enough slices -> the documented refusal, naming what it found
    with pytest.raises(ValueError, match="slice indices \\[0, 1\\]"):
        pick_multislice_devices(mocked, dcn_dp=3, per_slice=2)
    # enough slices but too few devices per slice
    with pytest.raises(ValueError, match="slice"):
        pick_multislice_devices(mocked, dcn_dp=2, per_slice=4)


def test_make_mesh_multislice_positive_branch(eight_devices):
    """make_mesh(dcn_dp=2) end to end on mock two-slice devices: the
    hybrid mesh comes back (2 slices x 4 chips) with the data axis — and
    ONLY the data axis — crossing slices."""
    devs = [_SliceDev(d, i // 4) for i, d in enumerate(eight_devices)]
    mesh = make_mesh(dp=4, tp=2, dcn_dp=2, devices=devs)
    assert mesh.axis_names == ("data", "model", "seq", "pipe")
    assert mesh.shape == {"data": 4, "model": 2, "seq": 1, "pipe": 1}
    grid = mesh.devices  # (4, 2, 1, 1)
    # the data axis factors (dcn x within-slice): rows 0-1 slice 0, rows
    # 2-3 slice 1 — crossing the data axis crosses slices at one boundary
    for m in range(2):
        assert {grid[i, m, 0, 0].slice_index for i in range(2)} == {0}
        assert {grid[i, m, 0, 0].slice_index for i in range(2, 4)} == {1}
        # model-axis neighbors NEVER cross slices
        for i in range(4):
            assert grid[i, 0, 0, 0].slice_index == grid[i, 1, 0, 0].slice_index


def test_tpu_create_device_mesh_failure_is_raised(monkeypatch):
    """A create_device_mesh failure on the full set of real chips must
    surface: a silent fall to list order would change which ICI links each
    axis rides."""

    class FakeTpu:
        platform = "tpu"

        def __init__(self, i):
            self.id = i
            self.coords = (i, 0, 0)

    fakes = [FakeTpu(i) for i in range(4)]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: fakes)
    from jax.experimental import mesh_utils

    def boom(shape, devices=None):
        raise NotImplementedError("unknown topology")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", boom)
    with pytest.raises(NotImplementedError, match="unknown topology"):
        mesh_mod._device_grid((4, 1, 1, 1), fakes)
