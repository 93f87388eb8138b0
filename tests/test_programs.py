"""The programs the benchmark reads by name, and the jits they come from.

The benchmark's device metrics find the turn in a trace by its program's
name: ``jit__hot_turn_impl`` for a pool turn (``turn_device_ms.batch`` /
``.open``), ``jit__sharded_full_turn`` and ``jit__sharded_sub_turn`` for a
sharded sweep's turns (``turn_device_ms.sweep``).  Every one is built by
``hotloop.programs`` from a family's record, so these tests pin

* that a pool's turn is the very jit the factory returns for its family
  with no mesh and no donation (the jit a single-device sweep runs), and
  lowers to ``jit__hot_turn_impl``;
* that a sweep sharded over a four-device CPU mesh runs programs named
  ``jit__sharded_full_turn`` / ``jit__sharded_sub_turn``, and that running
  the same sweep again compiles no new variant.

The sharded case runs in a child process, because the four CPU devices
must be asked for (``XLA_FLAGS``) before jax starts.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.engine import hotloop, session_pool
from repro.engine.session_pool import PoolConfig, SessionPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("selector,d", [("median", 2), ("maxmarg", 3),
                                        ("unified", 2)])
def test_pool_turn_is_the_factory_jit(selector, d):
    pool = SessionPool(PoolConfig(slots=4, k=2, n_pad=16, d=d,
                                  selector=selector, n_angles=32,
                                  max_epochs=4))
    assert pool.family is session_pool.FAMILIES[selector]
    fn, args, kw = pool.turn_call(np.arange(3))
    assert fn is hotloop.programs(pool.family)[1]
    assert fn is hotloop.programs(pool.family, donate=False)[1]
    head = fn.lower(*args, **kw).as_text().split(" ", 2)[:2]
    assert head == ["module", "@jit__hot_turn_impl"]


_SHARDED = r"""
import json, sys
import jax
from repro import engine
from repro.core import datasets
from repro.engine import hotloop, maxmarg, median
from repro.engine.state import (ProtocolInstance, device_put_sharded,
                                pack_instances, pack_instances_maxmarg,
                                shard_specs)
from repro.launch.mesh import make_data_mesh

family = sys.argv[1]
mesh = make_data_mesh(4)
gens = (datasets.data1, datasets.data2, datasets.data3)
insts = [ProtocolInstance(gens[i % 3](n_per_node=40, k=2, seed=i),
                          (0.1, 0.05, 0.02)[i % 3], family)
         for i in range(10)]
if family == "maxmarg":
    kw = dict(max_epochs=8, steps=300, warm=False)
    fam = maxmarg.FAMILY
    data, state, k, _ = pack_instances_maxmarg(insts, max_epochs=8,
                                               max_support=4, mesh=mesh)
    opts = dict(k=k, max_support=4, steps=300, stages=3, lam0=1e-3,
                per_node=False, fused_kernel=False, solver_kernel=False)
    consts, turn = (), dict(trans_width=8, warm=False)
else:
    kw = dict(n_angles=64, max_epochs=12)
    fam = median.FAMILY
    data, state, k, _ = pack_instances(insts, n_angles=64, max_epochs=12,
                                       mesh=mesh)
    opts = dict(k=k, cut_kernel=False, extremes_kernel=False)
    consts = (jax.ShapeDtypeStruct((64, 2), "float32"),)
    turn = dict(first_turn=False, trans_width=8)


def sweep():
    hotloop.KEY_LOG.clear()
    engine.run_sweep(insts, mesh=mesh, **kw)
    return sorted(map(list, set(hotloop.KEY_LOG)))


keys = sweep()
data, state = device_put_sharded(data, mesh), device_put_sharded(state, mesh)
full, sub = hotloop.programs(fam, True, mesh, shard_specs(data),
                             shard_specs(state), tuple(sorted(opts.items())))
sizes = [full._cache_size(), sub._cache_size()]
again = sweep()
sizes_again = [full._cache_size(), sub._cache_size()]
lanes = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
idx = jax.ShapeDtypeStruct((16,), "int32", sharding=lanes)
n_act = jax.ShapeDtypeStruct((4,), "int32", sharding=lanes)
names = [full.lower(data, *consts, state, **turn).as_text().split()[1],
         sub.lower(data, *consts, state, idx, n_act,
                   **turn).as_text().split()[1]]
print(json.dumps({"names": names, "keys": keys, "again": again,
                  "sizes": sizes, "sizes_again": sizes_again}))
"""


@pytest.mark.parametrize("family", ["maxmarg", "median"])
def test_sharded_sweep_programs_on_four_devices(family):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", _SHARDED, family],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["names"] == ["@jit__sharded_full_turn",
                            "@jit__sharded_sub_turn"]
    # the sweep ran sub-batch turns through the very jit the factory gives
    assert got["sizes"][1] > 0
    # the same sweep again: the same keys, and nothing compiled
    assert got["again"] == got["keys"]
    assert got["sizes_again"] == got["sizes"]
    assert sum(got["sizes"]) <= len(got["keys"])
