"""Compile rehearsal: the main-path Pallas kernels, compiled ahead of time
for a described (not attached) TPU v5e at the shapes ``kernels.ops``
dispatches on the main path.

Nothing runs — a pass says the chip's compiler accepts the kernel (block
tiling, VMEM, lowered primitives), not that it computes the right thing;
``chip_smoke.py`` checks results on the chip itself. ``ops._on_tpu`` is
steered to True inside each test so the wrappers take their TPU branch
(padding, tile choice, ``interpret=False``) while JAX itself stays on the
CPU. The topology is described inside a module fixture, never at import:
only the one test worker that runs this file loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import dpp
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_tpu(one_chip, monkeypatch):
    """compile_tpu(fn, *shapes) -> the compiled v5e executable's HLO text,
    with the persistent compilation cache off around the compile (an
    entry written for a described chip cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, "no Pallas kernel in the program"
        return text

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("batch", [1, 64])
def test_phase2_select_compiles(compile_tpu, batch):
    """Sampling hot path: the paper's N = 100 x 100 model at E|Y| = 10."""
    model = dpp.random_kron(jax.random.PRNGKey(0), (100, 100)).rescale(10.0)
    k = model.spectrum().suggested_k_max()
    compile_tpu(
        lambda us, g1, g2, ke: ops.phase2_select(us, (g1, g2), (100, 100),
                                                 ke, backend="pallas"),
        ((batch, k), F32), ((batch, 100, k), F32), ((batch, 100, k), F32),
        ((batch,), I32))


def test_sampling_draw_names_its_phase2_kernel(compile_tpu, one_chip):
    """The jitted draw behind ``Kron.sample`` at the paper's size: its
    fused phase-2 custom call carries the kernel name the device-trace
    readers look for (``phase2_select_pallas``)."""
    from repro.sampling.batched import _sample_batched
    model = dpp.random_kron(jax.random.PRNGKey(0), (100, 100)).rescale(10.0)
    k = model.spectrum().suggested_k_max()

    def draw(keys, l1, l2, v1, v2):
        return _sample_batched(keys, (l1, l2), (v1, v2), k)
    shapes = (((256, 2), jnp.uint32), ((100,), F32), ((100,), F32),
              ((100, 100), F32), ((100, 100), F32))
    lowered = jax.jit(draw).lower(*[
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes])
    calls = [ln for ln in lowered.as_text().splitlines()
             if "@tpu_custom_call" in ln]
    assert len(calls) == 1
    assert 'kernel_name = "phase2_select_pallas"' in calls[0]
    compiled = compile_tpu(draw, *shapes)
    assert re.search(r"%phase2_select_pallas[.\d]* = [^\n]*"
                     r'custom_call_target="tpu_custom_call"', compiled)


def test_kdpp_draw_compiles_with_its_phase2_kernel(compile_tpu, one_chip):
    """The two programs behind ``Kron.sample(key, 64, k=8)`` at the
    paper's size: the keep-probability table's N-step ESP scan, built
    once per (spectrum, k), and the draw that takes the (N, k+1) table
    as an operand (the k-trip conditional draw, then the fused phase-2
    kernel, named as the device-trace readers look for it)."""
    from repro.sampling.kdpp import _sample_kdpp_batched, keep_table_of
    N, k = 100 * 100, 8
    table = jax.jit(lambda l1, l2: keep_table_of((l1, l2), k)).lower(
        *[jax.ShapeDtypeStruct((100,), F32, sharding=one_chip)] * 2
    ).compile()
    assert [tuple(o.shape) for o in table.out_info] == [(N, k + 1), ()]

    def draw(keys, v1, v2, P, k0):
        return _sample_kdpp_batched(keys, (v1, v2), P, k0, k)
    compiled = compile_tpu(
        draw, ((64, 2), jnp.uint32), ((100, 100), F32), ((100, 100), F32),
        ((N, k + 1), F32), ((), I32))
    assert re.search(r"%phase2_select_pallas[.\d]* = [^\n]*"
                     r'custom_call_target="tpu_custom_call"', compiled)


def test_phase2_select_dense_compiles(compile_tpu):
    """One factor (a ``Dense`` model): the leading block is a single
    row, compiled unpadded; its 600 items span five lane tiles."""
    model = dpp.random_kron(jax.random.PRNGKey(0), (600,)).rescale(10.0)
    k = model.spectrum().suggested_k_max()
    compile_tpu(
        lambda us, g, ke: ops.phase2_select(us, (g,), (600,), ke,
                                            backend="pallas"),
        ((64, k), F32), ((64, 600, k), F32), ((64,), I32))


def test_greedy_map_compiles(compile_tpu):
    """``model.map`` / KV compaction: a (64, 64) Kron kernel, N = 4096."""
    compile_tpu(lambda L: ops.greedy_map_kdpp(L, 10), ((4096, 4096), F32))


@pytest.mark.parametrize("op", ["partial_trace_A", "partial_trace_C"])
def test_partial_trace_compiles(compile_tpu, op):
    """Dense-Θ KrK statistics at N = 64 x 64 (a 64 MiB Θ)."""
    fn = getattr(ops, op)
    compile_tpu(lambda theta, L: fn(theta, L, 64, 64),
                ((4096, 4096), F32), ((64, 64), F32))


def test_kron_matvec_compiles(compile_tpu):
    compile_tpu(ops.kron_matvec, ((100, 100), F32), ((100, 100), F32),
                ((8, 10000), F32))
