"""Compile the chip's programs for a described (not attached) TPU v5e.

The TPU compiler is installed with JAX, so the Pallas kernels and replay
programs can be compiled for a v5e that is only described: what Mosaic or
XLA would refuse on the chip is refused here, at no chip time.  Nothing
runs, so these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture (never at import)
and every compile happens in this test process; all of them live in this
one file.  Compiles go straight to the compiler: the persistent
compilation cache is off while this module's tests run.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import replay
from repro.core.eee import Policy, PowerModel
from repro.kernels.hist_update import hist_update_pallas
from repro.kernels.port_energy import port_energy_pallas
from repro.kernels.tpdt_select import tpdt_select_pallas
from repro.scenarios.spec import build_trace
from repro.scenarios.suite import resolve
from repro.topology.megafly import paper_topology, small_topology
from repro.traffic.plan import compile_plan, stack_plans

E, B = 1024, 200                      # events per port, histogram bins


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def port_ends():
    """Directed port-ends of the paper's 4160-node Megafly."""
    return 2 * paper_topology().n_links


def _sds(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("log_bins", [False, True])
def test_hist_update_compiles(one_chip, port_ends, log_bins):
    _compile_kernel(
        lambda g: hist_update_pallas(g, n_bins=B, bin_width=1e-5,
                                     log_bins=log_bins),
        _sds(one_chip, E, port_ends))


def test_port_energy_compiles(one_chip, port_ends):
    P = port_ends
    _compile_kernel(
        lambda g, d, t, tail, tds, hold: port_energy_pallas(
            g, d, t, tail, t_w=4.48e-6, t_s=2e-6, t_w2=1e-4, t_s2=1e-5,
            t_dst=tds, hold=hold),
        _sds(one_chip, E, P), _sds(one_chip, E, P), _sds(one_chip, P),
        _sds(one_chip, P), _sds(one_chip, P), _sds(one_chip, P))


def test_tpdt_select_compiles(one_chip, port_ends):
    P = port_ends
    _compile_kernel(
        lambda c, s, n, t, ctr: tpdt_select_pallas(
            c, s, n, t, ctr, max_tpdt=1e-2, tpdt_init=1e-3),
        _sds(one_chip, P, B), _sds(one_chip, P, B), _sds(one_chip, P),
        _sds(one_chip, P), _sds(one_chip, B))


@pytest.mark.parametrize("policy", [
    Policy(kind="fixed", t_pdt=1e-5, sleep_state="fast_wake"),
    Policy(kind="dual", t_pdt=1e-5, t_dst=2e-4, sleep_state="fast_wake",
           deep_state="deep_sleep"),
], ids=lambda p: p.kind)
def test_replay_segment_compiles(one_chip, policy):
    """The first replay segment of dc-poisson on the 80-node Megafly, as
    the (trace x policy) grid runs it, compiled with x64 time for v5e."""
    topo = small_topology()
    spec = resolve(["dc-poisson"])["dc-poisson"]
    batch = stack_plans([compile_plan(build_trace(spec, topo), topo)])
    proto, params, carry = replay.init_lanes_multi([policy], batch)
    seg = batch.segments[0]
    mode, needs_sort = replay._seg_flags(seg, proto)
    run = replay._multi_segment_runner(proto, PowerModel(), batch.n_links,
                                       seg.cap, mode, needs_sort)
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (carry[0], params, carry[1], carry[2], carry[3], batch.part_mask,
         seg.xs))
    compiled = run.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9
