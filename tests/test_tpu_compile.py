"""Compile the solve path's Pallas kernels and programs for a TPU v5e.

Nothing here runs on a chip: each case lowers and compiles for a
*described* v5e topology (``jax.experimental.topologies``), so the TPU
compiler refuses what interpret mode accepts — unaligned or 1-D blocks
under ``vmap``, scalar stores to VMEM, programs that outgrow device
memory. The topology is described inside a fixture only (never at
import), and every case skips where no v5e can be described.

Sizes are the one-chip deployment of the 16,000-vertex instance:
14-qubit subproblems, M = ceil(16000 / 13) = 1,231 of them, p = 3,
30 Adam steps (`ParaQAOAConfig` defaults).
"""

import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import compat
from repro.core import qaoa as qaoa_mod
from repro.kernels import cutvals, fused_layer, mixer, phase, tuning

N = 14
DIM = 2**N
M_SUBGRAPHS = 1231
BATCH = 8  # vmapped kernel cases: a handful of subgraphs is enough to lower
# Edges of one 14-vertex range of G(16000, 0.01): ~0.9 expected, a few at
# most; the cutvals kernel pads to one 256-edge chunk either way.
E_PAD = 8
# The whole solver batch must leave room on the 16 GB chip for the merge
# and refinement programs and the runtime's own buffers.
SOLVER_BUDGET_BYTES = 8 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or a held libtpu lock
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_case(name):
    """(fn, arg builder) of one main-path kernel at n = 14, unbatched."""
    if name == "apply_phase":
        fn = lambda re, im, c, g: phase.apply_phase(re, im, c, g)
        args = lambda s: (_f32((DIM,), s), _f32((DIM,), s), _f32((DIM,), s),
                          _f32((), s))
    elif name == "expectation":
        fn = lambda re, im, c: phase.expectation(re, im, c)
        args = lambda s: (_f32((DIM,), s),) * 3
    elif name == "vdot":
        fn = lambda a, b: phase.vdot(a, b)
        args = lambda s: (_f32((DIM,), s),) * 2
    elif name in ("mixer_lo0", "mixer_strided"):
        lo = 0 if name == "mixer_lo0" else 7
        fn = lambda re, im, b: mixer.apply_mixer_bits(re, im, N, lo, 7, b)
        args = lambda s: (_f32((DIM,), s), _f32((DIM,), s), _f32((), s))
    elif name == "fused_layer":
        rows = DIM // 128
        fn = lambda re, im, c, g, b: fused_layer.fused_phase_mixer_group(
            re, im, c, g, b, 7)
        args = lambda s: (_f32((rows, 128), s),) * 3 + (_f32((), s),) * 2
    elif name == "cutvals":
        fn = lambda e, w: cutvals.cutvals(N, e, w)
        args = lambda s: (_i32((E_PAD, 2), s), _f32((E_PAD,), s))
    elif name == "cutvals_at":
        fn = lambda idx, e, w: cutvals.cutvals_at(idx, e, w)
        args = lambda s: (_i32((DIM,), s), _i32((E_PAD, 2), s),
                          _f32((E_PAD,), s))
    else:
        raise ValueError(name)
    return fn, args


KERNELS = ["apply_phase", "expectation", "vdot", "mixer_lo0",
           "mixer_strided", "fused_layer", "cutvals", "cutvals_at"]


@pytest.mark.parametrize("batched", [False, True], ids=["single", "vmap"])
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name, batched):
    fn, args = _kernel_case(name)
    structs = args(one_chip)
    if batched:
        fn = jax.vmap(fn)
        structs = tuple(
            jax.ShapeDtypeStruct((BATCH,) + a.shape, a.dtype, sharding=one_chip)
            for a in structs
        )
    compiled = _compile(fn, *structs)
    assert "tpu_custom_call" in compiled.as_text()


def _solver_structs(m, sharding):
    return (_i32((m, E_PAD, 2), sharding), _f32((m, E_PAD), sharding),
            _i32((m,), sharding))


def test_solver_batch_compiles_for_v5e_within_budget(one_chip):
    cfg = qaoa_mod.QAOAConfig(n_qubits=N)
    program = qaoa_mod._solve_subgraph_batch_program(
        cfg, "pallas", tuning.state(), False)
    compiled = program.lower(*_solver_structs(M_SUBGRAPHS, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < SOLVER_BUDGET_BYTES, mem


def test_solve_pool_compiles_on_four_chip_mesh(topo):
    mesh = compat.make_mesh((4,), ("data",), devices=topo.devices)
    data = NamedSharding(mesh, P("data"))
    cfg = qaoa_mod.QAOAConfig(n_qubits=N)
    m_pad = -(-M_SUBGRAPHS // 4) * 4
    program = qaoa_mod._solve_subgraph_batch_program(
        cfg, "pallas", tuning.state(), False, mesh, ("data",))
    compiled = program.lower(*_solver_structs(m_pad, data)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert "all-gather" not in compiled.as_text()  # each chip keeps its rows


def test_four_solver_pool_of_17_qubits_fits_each_chip(topo):
    """The pool of the four-chip deployment (`n_solvers` 4): 1,000
    subgraphs of 17 qubits, 250 a chip, within the solver budget on each
    chip, where one chip would need all 1,000."""
    mesh = compat.make_mesh((4,), ("data",), devices=topo.devices)
    cfg = qaoa_mod.QAOAConfig(n_qubits=17)
    program = qaoa_mod._solve_subgraph_batch_program(
        cfg, "pallas", tuning.state(), False, mesh, ("data",))
    structs = _solver_structs(1000, NamedSharding(mesh, P("data")))
    compiled = program.lower(*structs).compile()
    assert "all-gather" not in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < SOLVER_BUDGET_BYTES, mem


def test_refine_sweeps_compile_for_v5e(one_chip):
    """The refinement program of the 16,000-vertex instance: G(16000, 0.01)
    has 1,279,920 edges and a largest degree near 220 (bucket 256)."""
    ls = importlib.import_module("repro.core.baselines.local_search")
    n, e = 16000, 1279920
    compiled = ls._sweeps.lower(
        _i32((2 * e,), one_chip), _f32((2 * e,), one_chip),
        _i32((n + 1,), one_chip), _f32((n,), one_chip), _i32((n,), one_chip),
        _f32((), one_chip), 200, 256).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2**30, mem
