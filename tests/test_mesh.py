"""launch/mesh.py: which mesh axes carry rows, and the devices the
partitioned fit takes from them."""
import jax
import numpy as np
import pytest

from repro.launch.mesh import data_axes, make_host_mesh, partition_devices
from repro.utils import make_auto_mesh


# every local device on the data axis; the others sized 1
@pytest.mark.parametrize("axes,want", [
    (("data",), ("data",)),
    (("data", "model"), ("data",)),
    (("pod", "data", "model"), ("pod", "data")),
])
def test_data_axes_and_partition_devices(axes, want):
    n = len(jax.devices())
    shape = tuple(n if a == "data" else 1 for a in axes)
    mesh = make_auto_mesh(shape, axes)
    assert data_axes(mesh) == want
    devs = partition_devices(mesh)
    assert len(devs) == n and len(set(devs)) == n
    assert list(devs) == list(np.asarray(mesh.devices).flat)
    assert data_axes(make_host_mesh()) == ("data",)
