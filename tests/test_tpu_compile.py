"""Compile every main-path Pallas kernel for a described TPU v5e chip.

Nothing runs: each test lowers one kernel wrapper at real widths (``R=8``,
``K=128``, the fused engine's ``SCATTER_MAX_NPAD``, the partitioned
engine's default slab, ``B=8`` planes) and asks the TPU compiler to build
it, which refuses illegal block shapes, unlowerable primitives, 64-bit
types and VMEM overruns that interpret mode cannot see.  The suite runs
with x64 on (``conftest.py``), so the kernels must stay int32-clean.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import prop_round as kern

R, K, B = 8, 128, 8
T = 64  # grid length: the compile does not depend on it
F32 = jnp.float32
I32 = jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-device compile cannot be read back from the persistent
    # cache without a chip; keep the cache out of these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _kernel_calls(text):
    """Base names (``%name.N`` less ``%`` and ``.N``) of the compiled
    program's Mosaic custom calls."""
    return [
        re.match(r"\s*%?([\w.-]+?)(\.\d+)? = ", line).group(1)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


def _compile(sharding, fn, *shapes):
    """Lower + compile ``fn`` for the described chip; returns the text.
    Every Mosaic kernel in it carries its name from ``KERNEL_NAMES``."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text)
    assert calls and set(calls) <= set(kern.KERNEL_NAMES.values()), calls
    return text


def _tiles(n_pad, k=K):
    """Tile-stream shapes at the engine's own index widths (compact int16
    columns / int8 integrality marks where ``prepare_block_ell`` narrows
    them for float32)."""
    compact = n_pad <= ops._COMPACT_COL_MAX_NPAD
    col = jnp.int16 if compact else I32
    ii = jnp.int8 if compact else I32
    return [((T, R, k), F32), ((T, R, k), col), ((T, R, k), ii)]


@pytest.mark.parametrize("n_pad", [16384, ops.SCATTER_MAX_NPAD])
def test_fused_scatter_compiles(one_chip, n_pad):
    def fn(val, col, ii, lhs, rhs, lb, ub):
        return kern.fused_scatter_round_tiles(
            val, col, ii, lhs, rhs, lb, ub, n_pad, 1e-6, interpret=False
        )

    _compile(one_chip, fn, *_tiles(n_pad), ((T, R), F32), ((T, R), F32),
             ((n_pad,), F32), ((n_pad,), F32))


def test_activities_gather_candidates_scatter_compile(one_chip):
    n_pad = ops.SCATTER_MAX_NPAD

    def fn(val, col, ii, lhs, rhs, lb, ub):
        mf, mc, xf, xc = kern.activities_gather_tiles(
            val, col, lb, ub, n_pad, interpret=False
        )
        return kern.candidates_scatter_tiles(
            val, col, ii, mf, mc, xf, xc, lhs, rhs, lb, ub, n_pad, 1e-6,
            interpret=False,
        )

    _compile(one_chip, fn, *_tiles(n_pad), ((T, R), F32), ((T, R), F32),
             ((n_pad,), F32), ((n_pad,), F32))


def test_apply_updates_compiles(one_chip):
    n_pad = ops.SCATTER_MAX_NPAD

    def fn(lb, ub, bl, bu):
        return kern.apply_updates_tiles(
            lb, ub, bl, bu, 1e-5, interpret=False, outward=2.0**-17
        )

    _compile(one_chip, fn, *[((n_pad,), F32)] * 4)


def test_batched_fused_scatter_compiles(one_chip):
    n_pad = 16384

    def fn(val, col, ii, lhs, rhs, lb, ub, inst, act):
        return kern.batched_occupancy_round_tiles(
            val, col, ii, lhs, rhs, lb, ub, inst, act, n_pad, 1e-5, 1e-6,
            interpret=False, outward=2.0**-17,
        )

    _compile(one_chip, fn, *_tiles(n_pad), ((T, R), F32), ((T, R), F32),
             ((B, n_pad), F32), ((B, n_pad), F32), ((T,), I32), ((B,), jnp.bool_))


@pytest.mark.parametrize("n_pad,k", [(ops.SCATTER_MAX_NPAD, K), (128, 8)])
def test_node_fused_scatter_compiles(one_chip, n_pad, k):
    """Real widths, and the narrow ``K=8`` tiles ``solve()`` prepares."""

    def fn(val, col, ii, lhs, rhs, lb, ub, act):
        bl, bu = kern.node_fused_scatter_round_tiles(
            val, col, ii, lhs, rhs, lb, ub, act, n_pad, 1e-6, interpret=False
        )
        return kern.apply_updates_batch_tiles(
            lb, ub, bl, bu, act, 1e-5, interpret=False, outward=2.0**-17
        )

    _compile(one_chip, fn, *_tiles(n_pad, k), ((T, R), F32), ((T, R), F32),
             ((B, n_pad), F32), ((B, n_pad), F32), ((B,), jnp.bool_))


def test_slab_round_and_partials_compile(one_chip):
    n_pad = 3 * ops.SCATTER_MAX_NPAD
    slab = ops.default_slab_width(n_pad)
    n_slabs = -(-n_pad // slab)
    part = n_slabs * slab
    runs, run_len = n_slabs, T // n_slabs

    def fn(val, col, ii, done, smf, smc, lhs, rhs, st, ln, ri, rs, act, lb, ub):
        mf, mc, xf, xc = kern.batched_slab_partials_tiles(
            val, col, st, ln, ri, rs, act, lb, ub, slab, run_len,
            interpret=False,
        )
        return kern.batched_slab_round_tiles(
            val, col, ii, done, mf, mc, xf, xc, lhs, rhs, st, ln, ri, rs, act,
            lb, ub, slab, run_len, 1e-5, 1e-6, interpret=False,
            outward=2.0**-17,
        )

    _compile(
        one_chip, fn, ((T, R, K), F32), ((T, R, K), I32), ((T, R, K), I32),
        ((T, R), I32), ((T, R), F32), ((T, R), I32), ((T, R), F32), ((T, R), F32),
        *[((runs,), I32)] * 4, ((1,), I32), ((1, part), F32), ((1, part), F32),
    )


def test_node_objective_compiles(one_chip):
    n_pad = ops.SCATTER_MAX_NPAD

    def fn(lb, ub, c, ii, valid):
        return kern.node_objective_tiles(
            lb, ub, c, ii, valid, 1e-8, interpret=False
        )

    _compile(one_chip, fn, ((B, n_pad), F32), ((B, n_pad), F32),
             ((n_pad,), F32), ((n_pad,), jnp.bool_), ((n_pad,), jnp.bool_))


def _presolve_runner(p, scatter):
    """The cached, jitted fixed point ``propagate_block_ell`` builds for
    ``p`` with Mosaic kernels at float32 (running it needs the chip)."""
    prep = ops.prepare_block_ell(p, dtype=F32)
    try:
        ops.propagate_block_ell(
            p, dtype=F32, interpret=False, donate=True, scatter=scatter
        )
    except Exception:  # Mosaic kernels do not run on the CPU
        pass
    return next(
        runner for key, (_, runner) in ops._runner_cache._d.items()
        if key[0] == id(prep.d.val) and key[4] == scatter
    ), prep.n_pad


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("scatter", ["fused", "partitioned"])
def test_presolve_fixed_point_is_named(one_chip, scatter):
    """The compiled fixed point is the module ``jit_fixed_point_presolve``
    and each kernel in its while body keeps its own name (no ``%body.N``
    custom call)."""
    from repro.data import make_mixed

    runner, n_pad = _presolve_runner(make_mixed(m=200, n=300, seed=3), scatter)
    args = [jax.ShapeDtypeStruct((n_pad,), F32, sharding=one_chip)] * 2
    text = runner.lower(*args).compile().as_text()
    assert text.startswith("HloModule jit_fixed_point_presolve")
    calls = _kernel_calls(text)
    assert calls and set(calls) <= set(kern.KERNEL_NAMES.values()), calls
    assert "%body." not in "\n".join(
        ln for ln in text.splitlines() if "tpu_custom_call" in ln
    )


def test_node_fixed_point_is_named(one_chip):
    """The node runner compiles as ``jit_fixed_point_nodes`` with the
    node kernel under its own name."""
    from repro.data import make_set_cover

    p = make_set_cover(200, 150, seed=0)
    prep = ops.prepare_block_ell(p, dtype=F32)
    assert prep.fits_one_chunk  # the Pallas node kernel, not the XLA round
    runner = ops.node_batch_runner(prep, B, interpret=False, donate=True)
    args = [jax.ShapeDtypeStruct((B, prep.n_pad), F32, sharding=one_chip)] * 2
    text = runner.lower(*args).compile().as_text()
    assert text.startswith("HloModule jit_fixed_point_nodes")
    assert kern.KERNEL_NAMES["node_fused_scatter"] in _kernel_calls(text)
    assert set(_kernel_calls(text)) <= set(kern.KERNEL_NAMES.values())


def test_float64_pallas_compile_raises():
    """Mosaic has no 64-bit types: a TPU compile of an f64 kernel refuses
    with a clear error instead of falling back to interpret mode."""
    x = jnp.zeros((2, R, K), jnp.float64)
    with pytest.raises(TypeError, match="float64 cannot run in a Pallas kernel"):
        kern.activities_tiles(x, x, x, interpret=False)
    np.testing.assert_array_equal(
        np.asarray(kern.activities_tiles(x, x, x, interpret=True)[0]), 0.0
    )


@pytest.mark.parametrize("entry", ["propagate_block_ell", "service"])
def test_float64_pallas_request_on_tpu_raises(monkeypatch, entry):
    """float64 with the Pallas engines on a TPU raises at the entry point,
    whichever round the instance's shape selects, instead of running in
    interpret mode or in the jnp round."""
    from repro.core import PropagationService
    from repro.data import make_mixed
    from repro.kernels import propagate_block_ell

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p = make_mixed(m=40, n=30, seed=0)
    with pytest.raises(TypeError, match="float64 cannot run in a Pallas kernel"):
        if entry == "service":
            PropagationService.from_problems([p], dtype=np.float64)
        else:
            propagate_block_ell(p, dtype=np.float64)
