"""Every cell's chunk programs, compiled at the cell's own size for a
described TPU v5e (no chip attached): the cohort programs of each bucket
on one chip; and the sharded programs of a 32-member GP fleet on a mesh
of four, the cell a later change adds (PERF.md, Open questions).  A compile that passes is not
a chip run; it catches what the chip's compiler refuses (tiles, fast
memory, device memory) before a chip call is spent on it.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import concurrent.futures as cf

import jax
import numpy as np
import pytest

from harness import cell, system

CELLS = [("paper-gp.google-c8", 1, None), ("paper-baseline.google-c8", 1, None),
         ("paper-gp.google-c8", 4, 32)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    from repro.sim.compile_cache import cold_compiles
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without that chip: keep the cache out of it
        with cold_compiles():
            yield t


@pytest.fixture
def pallas_on_tpu(monkeypatch):
    """Steer the Gram's ``impl="auto"`` to the kernel, as on the chip."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _shapes(cfg, c, sharding):
    """The cell's trace and state as shapes, placed by ``sharding``."""
    from repro.sim.state import DeviceTrace, init_state
    B, N = int(c.mix["members"]), int(c.spec["n_apps"])
    C = int(c.mix["params"]["max_components"])
    tr = jax.eval_shape(lambda: DeviceTrace.from_traces([_blank(N, C)] * B))
    st = jax.eval_shape(lambda: init_state(cfg, N, C, batch=B))
    put = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)  # noqa: E731
    return jax.tree.map(put, tr), jax.tree.map(put, st)


def _blank(N, C):
    from repro.sim.scenarios.schema import SEGMENTS, Trace
    z = np.zeros
    return Trace(submit=z(N, np.float32), is_elastic=z(N, bool), is_jumpy=z(N, bool),
                 n_core=z(N, np.int64), n_elastic=z(N, np.int64), runtime=z(N, np.float32),
                 cpu_req=z((N, C), np.float32), mem_req=z((N, C), np.float32),
                 is_core=z((N, C), bool), levels=z((N, C, SEGMENTS, 2), np.float32))


@pytest.mark.parametrize("name,chips,members", CELLS)
def test_cell_programs_compile_for_v5e(topo, pallas_on_tpu, name, chips, members):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

    from repro.sim import step as S
    c = cell.load_cell(name)
    c.chips = chips
    if members:
        c.mix = dict(c.mix, members=members)
    cfg = system.sim_config(c.spec)
    C = int(c.mix["params"]["max_components"])
    shapes = (int(c.spec["n_apps"]), C, cfg.cluster.max_running_apps, cfg.window)
    if c.chips == 1:
        tr, st = _shapes(cfg, c, SingleDeviceSharding(topo.devices[0]))
        fns = {b: S._chunk_fn(cfg, system.CHUNK, shapes, True, b)
               for b in system.buckets(cfg, cfg.cluster.max_running_apps * C)}
    else:
        mesh = Mesh(np.array(topo.devices[:c.chips]), (S.FLEET_AXIS,))
        tr, st = _shapes(cfg, c, NamedSharding(mesh, PartitionSpec(S.FLEET_AXIS)))
        fns = {b: S._shard_chunk_fn(cfg, system.CHUNK, shapes, mesh, b)
               for b in system.buckets(cfg, cfg.cluster.max_running_apps * C)}

    lowered = {b: fn.__wrapped__.lower(tr, st) for b, fn in fns.items()}
    with cf.ThreadPoolExecutor(len(lowered)) as ex:
        compiled = dict(zip(lowered, ex.map(lambda lo: lo.compile(), lowered.values())))
    for b, prog in compiled.items():
        text = prog.as_text()
        assert ("tpu_custom_call" in text) == (c.spec["sim"]["forecaster"] == "gp"
                                               and c.spec["sim"]["policy"] != "baseline"), b
        mem = prog.memory_analysis()
        assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
