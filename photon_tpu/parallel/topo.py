"""Abstract TPU topologies via the in-image libtpu — no chip attached.

``jax.experimental.topologies`` + libtpu's AOT topology support yield real
"TPU v5 lite" device objects any sharded program can be compiled against
(scripts/aot_compile_check.py, tests/test_tpu_compile.py). libtpu wants the
env a real TPU VM would have; this helper sets it for the duration of the
topology construction and restores anything it overwrote.
"""

from __future__ import annotations

import os


def abstract_tpu_devices(topology: str = "v5e:2x2x1") -> list:
    """Device list for an abstract v5e topology (e.g. ``"v5e:4x8x1"``).

    Raises ``RuntimeError`` with an actionable message when the local
    libtpu/topology machinery is unavailable (callers that can degrade —
    tests — catch and skip).
    """
    from jax.experimental import topologies

    if ":" not in topology:
        raise ValueError(f"topology must look like 'v5e:2x2x1', got {topology!r}")
    # v5e is a 2D generation: a trailing literal x1 dimension is sugar
    # ("2x4x1" == "2x4") — strip exactly that, never a substring
    shape = topology.split(":", 1)[1]
    parts = shape.split("x")
    if topology.startswith("v5e:") and len(parts) == 3 and parts[2] == "1":
        shape = "x".join(parts[:2])

    # TPU_SKIP_MDS_QUERY avoids the GCP metadata-server query that hangs
    # off-VM; the accelerator type sets the 2x2 host bounds every v5e shape
    # must divide
    overrides = {
        "TPU_SKIP_MDS_QUERY": os.environ.get("TPU_SKIP_MDS_QUERY", "1"),
        "TPU_ACCELERATOR_TYPE": os.environ.get("TPU_ACCELERATOR_TYPE",
                                               "v5litepod-4"),
        "TPU_WORKER_HOSTNAMES": "localhost",
        "TPU_TOPOLOGY": shape,
    }
    prior = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name=topology)
        return list(topo.devices)
    except Exception as e:  # noqa: BLE001 — normalize for degrading callers
        raise RuntimeError(
            f"abstract TPU topology {topology!r} unavailable "
            f"(libtpu missing or incompatible): {e}"
        ) from e
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
