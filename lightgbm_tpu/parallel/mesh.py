"""Device mesh helpers.

TPU-native replacement for the reference's network bring-up
(reference: src/network/network.cpp Network::Init, linkers_socket.cpp —
machine lists, listen ports, full TCP mesh).  On TPU the SPMD context is a
jax.sharding.Mesh over the slice's chips; multi-host bring-up is
jax.distributed.initialize, and the collectives ride ICI/DCN via XLA.

The reference's network params (num_machines, machines, local_listen_port,
time_out, machine_list_filename) are accepted by the config layer and
translated: num_machines>1 simply asserts the mesh is large enough.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"  # rows (reference: tree_learner=data rank axis)


def make_mesh(n_devices: Optional[int] = None, devices: Optional[Sequence] = None) -> Mesh:
    """1-D data mesh over the available chips."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (DATA_AXIS,))


def data_axis_size(mesh: Mesh) -> int:
    """Ranks along the data axis (local rows = padded rows // R)."""
    return int(mesh.shape[DATA_AXIS])
