"""Compile every Pallas kernel, and the stepped chunk programs, for a TPU v5e
that is described rather than attached.

Interpret mode cannot see what Mosaic refuses (unsigned reductions, boolean
loop carries, primitives with no TPU lowering, scoped-VMEM overflow), so
these compiles guard the chip path at no chip time. Each kernel compiles at
n = 1024 with ``interpret=False`` and must show up as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file. The persistent compilation cache is off around these compiles
(an entry written for a described chip cannot be read back without one).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.compaction import spec_fns
from repro.core.problem import ASSIGNMENT, OT
from repro.kernels import cost_matrix as cm
from repro.kernels import fused_phase as fp
from repro.kernels import sinkhorn_step as ss
from repro.kernels import slack_propose as sp

N = 1024
B = 4
I32 = jnp.int32
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _kernel_case(name, S):
    """(fn, abstract args) for one pallas_call entry point at n = N."""
    if name == "cost_matrix":
        return (lambda x, y: cm.cost_matrix(x, y, interpret=False),
                (S((N, 2), F32), S((N, 2), F32)))
    if name == "cost_matrix_batched":
        return (lambda x, y: cm.cost_matrix_batched(x, y, interpret=False),
                (S((B, N, 2), F32), S((B, N, 2), F32)))
    if name == "slack_propose":
        return (lambda c, yb, ya, av, s: sp.slack_propose(
                    c, yb, ya, av, s, interpret=False),
                (S((N, N), I32), S((N,), I32), S((N,), I32),
                 S((N,), jnp.bool_), S((), I32)))
    if name == "slack_propose_batched":
        return (lambda c, yb, ya, av, s: sp.slack_propose_batched(
                    c, yb, ya, av, s, interpret=False),
                (S((B, N, N), I32), S((B, N), I32), S((B, N), I32),
                 S((B, N), jnp.bool_), S((B,), I32)))
    if name == "sinkhorn_row_update":
        return (lambda c, g, lognu, reg: ss.sinkhorn_row_update(
                    c, g, lognu, reg, interpret=False),
                (S((N, N), F32), S((N,), F32), S((N,), F32), S((), F32)))
    if name == "fused_assignment_phases":
        return (lambda c, mba, mab, yb, ya, sc: fp.fused_assignment_phases(
                    c, mba, mab, yb, ya, *sc, k=8, interpret=False),
                (S((N, N), I32), S((N,), I32), S((N,), I32), S((N,), I32),
                 S((N,), I32), tuple(S((), I32) for _ in range(6))))
    if name == "fused_ot_phases":
        return (lambda c, yb, yahi, fb, fa, fhi, flo, sc: fp.fused_ot_phases(
                    c, yb, yahi, fb, fa, fhi, flo, *sc, k=8,
                    max_rounds=2 * N + 2, interpret=False),
                (S((N, N), I32), S((N,), I32), S((N,), I32), S((N,), I32),
                 S((N,), I32), S((N, N), I32), S((N, N), I32),
                 tuple(S((), I32) for _ in range(4))))
    raise ValueError(name)


KERNELS = ("cost_matrix", "cost_matrix_batched", "slack_propose",
           "slack_propose_batched", "sinkhorn_row_update",
           "fused_assignment_phases", "fused_ot_phases")


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, shape):
    fn, args = _kernel_case(name, shape)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("spec", [ASSIGNMENT, OT], ids=["assignment", "ot"])
def test_stepped_chunk_compiles_for_v5e(spec, shape):
    """The default path's k-phase chunk program (vmapped over a batch),
    exactly as the compacting driver dispatches it."""
    prologue, init, chunk, _, _ = spec_fns(spec, 8)
    inputs = {"c": np.zeros((B, N, N), np.float32)}
    if spec is OT:
        inputs["nu"] = np.full((B, N), 1.0 / N, np.float32)
        inputs["mu"] = np.full((B, N), 1.0 / N, np.float32)
    ops = {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
           for k, v in spec.prepare(spec.canonicalize(inputs), 0.1).ops.items()}
    data, ctx = jax.eval_shape(prologue, ops)
    state = jax.eval_shape(init, data, ctx)
    placed = jax.tree.map(lambda a: shape(a.shape, a.dtype), (data, state))
    compiled = chunk.lower(*placed).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > B * N * N * 4


def test_mesh_lane_placement_compiles_for_v5e(topo):
    """The batch-mesh driver writes a bucket back into its sharded result
    buffer with per-device gathers (``distributed._place_into``), never a
    partitioned scatter, on the four chips of a v5e:2x2."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.distributed import _place_into

    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("data",))
    b, nb, n = 32, 16, 512

    def arg(dims, spec):
        return jax.ShapeDtypeStruct(dims, I32,
                                    sharding=NamedSharding(mesh, spec))

    buf = {"y_b": arg((b, n), P("data")), "f": arg((b, n, n), P("data")),
           "phases": arg((b,), P("data"))}
    tree = {"y_b": arg((nb, n), P()), "f": arg((nb, n, n), P()),
            "phases": arg((nb,), P())}
    text = _place_into(mesh, "data").lower(
        buf, tree, arg((b,), P("data"))).compile().as_text()
    assert "scatter" not in text


def test_fused_refuses_shape_beyond_vmem():
    """A shape the fused kernel cannot hold is refused while tracing, with
    an error that names the way out, not deep inside Mosaic."""
    n = 2048
    args = [jax.ShapeDtypeStruct((n, n), I32)] + [
        jax.ShapeDtypeStruct((n,), I32)] * 4 + [
        jax.ShapeDtypeStruct((n, n), I32)] * 2
    with pytest.raises(ValueError, match="stepped core"):
        jax.eval_shape(
            lambda c, yb, yahi, fb, fa, fhi, flo: fp.fused_ot_phases(
                c, yb, yahi, fb, fa, fhi, flo, 0, 0, 0, 1, k=1,
                max_rounds=4, interpret=False),
            *args)


def test_fused_interpret_mode_has_no_vmem_cap():
    """The VMEM cap belongs to the compiled kernel: interpret mode (the
    default off a TPU) still traces the shape the compiled kernel refuses."""
    n = 2048
    args = [jax.ShapeDtypeStruct((n, n), I32)] + [
        jax.ShapeDtypeStruct((n,), I32)] * 4 + [
        jax.ShapeDtypeStruct((n, n), I32)] * 2
    out = jax.eval_shape(
        lambda c, yb, yahi, fb, fa, fhi, flo: fp.fused_ot_phases(
            c, yb, yahi, fb, fa, fhi, flo, 0, 0, 0, 1, k=1,
            max_rounds=4, interpret=True),
        *args)
    assert out[4].shape == (n, n)
