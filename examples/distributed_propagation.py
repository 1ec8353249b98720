"""Distributed propagation demo: the nonzero-partitioned fixed point under
shard_map on a mesh over every device present, matching the single-device
limit point.

  PYTHONPATH=src python examples/distributed_propagation.py

On a CPU host, give XLA several devices to split over first, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=4``; on a TPU host the
mesh spans its chips.
"""
import numpy as np
import jax

jax.config.update("jax_enable_x64", True)

from repro.core import bounds_equal, propagate, propagate_sharded
from repro.data import make_mixed

mesh = jax.make_mesh((len(jax.devices()),), ("data",))
print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
      f"({jax.devices()[0].platform})")

p = make_mixed(m=2000, n=1500, seed=42)
print(f"instance: m={p.m} n={p.n} nnz={p.nnz}")

r1 = propagate(p, driver="device_loop")
r2 = propagate_sharded(p, mesh)

print(f"single-device : rounds={int(r1.rounds)} converged={bool(r1.converged)}")
print(f"sharded ({mesh.devices.size} dev): rounds={int(r2.rounds)} "
      f"converged={bool(r2.converged)}")
print("limit points equal:",
      bounds_equal(np.asarray(r1.lb), np.asarray(r1.ub),
                   np.asarray(r2.lb), np.asarray(r2.ub)))
tight = int(np.sum(np.asarray(r2.lb) > p.lb + 1e-9)
            + np.sum(np.asarray(r2.ub) < p.ub - 1e-9))
print(f"bounds tightened: {tight}")
