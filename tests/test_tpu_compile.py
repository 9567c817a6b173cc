"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Interpret mode (the rest of the suite) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tiling, gathers Mosaic cannot lower,
too much VMEM. These tests lower and compile for one chip of a described
``v5e:2x2`` topology — no chip needed, nothing runs — and check that the
kernel is in the compiled program (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. The library's own log files are turned off (``TPU_LOG_DIR``),
so a run writes nothing outside its checkout.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled.as_text()


@pytest.mark.parametrize("n", [36, 64, 256])
def test_minplus_compiles_for_v5e(one_chip, n):
    from repro.kernels.minplus import minplus

    x = jax.ShapeDtypeStruct((64, n, n), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compile(lambda a: minplus(a, a), x)


@pytest.mark.parametrize("n", [64, 256])
def test_walk_accumulate_compiles_for_v5e(one_chip, n):
    from repro.kernels.link_util import walk_accumulate

    s = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = _compile(
        lambda nh, f, dl: walk_accumulate(nh, f, dl, max_hops=24),
        s((n, n), jnp.int32), s((n, n), jnp.float32), s((n, n), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("spec_name", ["spec_64", "spec_large"])
def test_single_device_apsp_compiles_for_v5e(one_chip, spec_name):
    """The batched APSP as ``Evaluator.batch_aux`` dispatches it on one
    chip (its own jit, ``backend="pallas"``, the spec's squaring count)."""
    from repro.core import problem, routing
    from repro.core.objectives import make_consts

    spec = getattr(problem, spec_name)()
    n, b = spec.n_tiles, 64
    iters = make_consts(spec).apsp_iters
    x = jax.ShapeDtypeStruct((b, n, n), jnp.float32, sharding=one_chip)
    text = _compile(
        lambda c: routing.apsp_batched(c, iters, backend="pallas"), x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_evaluator_step_compiles_for_v5e(one_chip, backend):
    """The spmd executor's per-shard body at spec_64 (cost build → batched
    APSP → objective walk in one program); the Pallas APSP kernel is in it
    exactly when the routing backend is ``pallas``, which ``auto`` resolves
    to on a TPU. The single-device path dispatches the same stages as
    separate jits (its APSP is compiled above)."""
    from repro.core import spec_64
    from repro.core.evaluate import batch_pipeline
    from repro.core.objectives import make_consts

    spec = spec_64()
    n, b = spec.n_tiles, 64
    s = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = _compile(partial(batch_pipeline, make_consts(spec), backend, False),
                    s((b, n), jnp.int32), s((b, n, n), jnp.bool_),
                    s((n, n), jnp.float32))
    assert ("tpu_custom_call" in text) == (backend == "pallas")


def test_spmd_evaluator_compiles_for_four_v5e_chips(topo):
    """The spmd executor's program (Evaluator under ``spmd_scope``): the
    compiled APSP kernel inside ``jax.shard_map`` over a 4-chip mesh, with
    the varying-axes check on, and the batch split across the four."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import Evaluator, spec_64, traffic_matrix
    from repro.core.evaluate import spmd_scope

    spec = spec_64()
    n, b = spec.n_tiles, 16
    mesh = Mesh(np.array(topo.devices), ("dev",))
    with spmd_scope(mesh):
        ev = Evaluator(spec, traffic_matrix(spec, "BFS"), backend="pallas")
    rows, rep = NamedSharding(mesh, P("dev")), NamedSharding(mesh, P())
    compiled = ev._spmd_fn.lower(
        jax.ShapeDtypeStruct((b, n), jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((b, n, n), jnp.bool_, sharding=rows),
        jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=rep)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert len(compiled.output_shardings[0].device_set) == 4
