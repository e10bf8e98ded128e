"""TPU compiles of the simulator's main path at real widths, for a
described v5e chip (no chip attached): the Pallas Gram kernel at the GP's
padded shapes, its custom-VJP gradient, and the GP ``forecast_batch`` of
one forecast bucket with the kernel selected.  Each compiles in about two
seconds and catches what interpret-mode tests cannot: tiles the chip's
compiler refuses, fast-memory limits, and a kernel with no reverse-mode
rule inside ``jax.grad``.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.core.forecast import GPForecaster, gp
from repro.kernels import gp_gram, ops
from repro.sim import SimConfig
from repro.sim.compile_cache import cold_compiles


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler would otherwise log under the temp directory
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without that chip: keep the cache out of it
        with cold_compiles():
            yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas_on_tpu(monkeypatch):
    """Steer ``impl="auto"`` and the interpret switch to the chip path
    (without a chip, the default backend is the CPU)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _holds_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m,n", [(16, 16), (8, 16)])
def test_gram_kernel_compiles(one_chip, m, n):
    """The GP's Gram tiles: X vs X (N = 10 -> 16) and a query row vs X."""
    fn = jax.jit(partial(gp_gram.gp_gram, kind="exp", bm=m, bn=n,
                         interpret=False))
    compiled = fn.lower(_spec((m, 128), one_chip), _spec((n, 128), one_chip),
                        _spec((1, 128), one_chip)).compile()
    assert _holds_kernel(compiled)


def test_gram_grad_compiles(one_chip, pallas_on_tpu):
    """The GP evidence gradient (gp.py's ``jax.grad``) with the kernel in
    its forward pass: the Cholesky's backward needs the Gram's value."""
    loss = partial(gp._neg_log_marginal, kernel="exp", jitter=1e-5,
                   impl="pallas")
    fn = jax.jit(jax.grad(loss))
    compiled = fn.lower(_spec((3,), one_chip), _spec((10, 11), one_chip),
                        _spec((10,), one_chip),
                        _spec((10,), one_chip, bool)).compile()
    assert _holds_kernel(compiled)


def test_gp_forecast_batch_compiles(one_chip, pallas_on_tpu):
    """One bucket of the paper's GP forecast: 2 x 8 monitor rows of a
    24-tick window, the kernel picked by ``impl="auto"``."""
    cfg = SimConfig()
    model = GPForecaster(cfg.gp)
    fn = jax.jit(lambda w, v: model.forecast_batch(w, cfg.horizon, valid=v))
    compiled = fn.lower(_spec((16, cfg.window), one_chip),
                        _spec((16, cfg.window), one_chip, bool)).compile()
    assert _holds_kernel(compiled)
