"""Compiles for a TPU v5e chip, made without the chip.

jaxlib's TPU plug-in carries the chip's compiler, so a program can be
compiled for a described (not attached) v5e and is refused here exactly
as the chip would refuse it.  Nothing runs, so these tests say nothing
about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every pytest worker imports every test file.  The persistent
compilation cache is off while these compiles run, because an entry
compiled for a described chip cannot be read back without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro
from repro import api
from repro.errors import PlanError, UnservableConfigError
from repro.kernels import ops
from repro.serve.crypto_engine import PolymulEngine


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_polymul(pl, sharding, rows, fn=api.polymul):
    """AOT-compile ``fn`` (default ``repro.polymul``) for ``rows``
    products of ``pl`` with every leaf and operand placed on
    ``sharding``."""
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
    z = jax.ShapeDtypeStruct(
        (rows, pl.n, pl.config.seg_count), jnp.int64, sharding=sharding
    )
    return jax.jit(fn).lower(jax.tree.map(on_chip, pl), z, z).compile()


def test_served_jnp_path_compiles_for_one_chip(one_chip):
    """The datapath ``backend="auto"`` serves on a TPU compiles for one
    v5e.  n=16/t=2 is the cheapest plan that still runs decompose ->
    NTT cascade -> compose; n=4096 costs about two minutes here."""
    pl = repro.plan(n=16, t=2, v=30)
    assert pl.config.backend == "jnp"
    compiled = _compile_polymul(pl, one_chip, rows=8)
    hlo = compiled.as_text()
    assert "tpu_custom_call" not in hlo  # plain XLA, no Pallas kernel
    for stage in ("decompose", "cascade", "compose"):
        assert f"parentt.{stage}" in hlo, stage
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30


def test_engine_result_is_int32_with_n_on_the_lanes(one_chip):
    """The engine's batch executable, compiled for one v5e, returns its
    result as int32 with n, not the L limbs, on the minor (lane) axis
    of the device layout: an int64 result read back 13–22x slower."""
    eng = PolymulEngine(batch_slots=8)
    pl = eng.plan(n=16, t=2, v=30)
    compiled = _compile_polymul(pl, one_chip, rows=8, fn=eng._exec)
    header = compiled.as_text().splitlines()[0]
    result = re.search(
        r"entry_computation_layout=\{.*\)->(\w+)\[([\d,]+)\]\{([\d,]+)", header
    )
    assert result, header
    dtype, dims, minor_to_major = result.groups()
    assert dtype == "s32"
    assert dims == f"8,16,{pl.config.L}"  # (batch_slots, n, L)
    assert minor_to_major.split(",")[0] == "1"  # n is the minor axis


@pytest.mark.parametrize("backend", ops.PALLAS_BACKENDS)
def test_pallas_backend_refused_at_plan_time_on_tpu(monkeypatch, backend):
    """On a TPU a Pallas backend asked for by name fails at plan time,
    with the reason and the servable alternatives, and ``auto`` is
    ``jnp``: no compile error can reach a dispatch or the breaker."""
    monkeypatch.setattr(ops, "_is_tpu", lambda: True)
    with pytest.raises(UnservableConfigError, match="int64") as ei:
        repro.plan(n=64, t=3, v=30, backend=backend)
    assert ei.value.knob == "backend"
    assert ei.value.alternatives == ("auto", "jnp")
    assert repro.plan(n=64, t=3, v=30).config.backend == "jnp"


# Mosaic has no 64-bit layout and every Pallas datapath computes on
# int64, so each refusal below is the chip's compiler saying no.  The
# 32-bit-lane datapath turns these into passes.
_REFUSED = [
    pytest.param(
        "pallas_fused_e2e", None,
        marks=pytest.mark.xfail(
            strict=True, raises=ValueError,
            reason="per-channel (None, 1) scalar block is not (8, 128)-aligned",
        ),
        id="fused_e2e-channel_grid",
    ),
    pytest.param(
        "pallas_fused_e2e", False,
        marks=pytest.mark.xfail(
            strict=True, raises=ValueError,
            reason="only 32-bit arrays convert to scalars, got int64",
        ),
        id="fused_e2e-unrolled",
    ),
    pytest.param(
        "pallas", None,
        marks=pytest.mark.xfail(
            strict=True, raises=ZeroDivisionError,
            reason="Mosaic block-mapping packing = 32 // 64 == 0",
        ),
        id="pallas",
    ),
    pytest.param(
        "pallas_fused", None,
        marks=pytest.mark.xfail(
            strict=True, raises=ZeroDivisionError,
            reason="Mosaic block-mapping packing = 32 // 64 == 0",
        ),
        id="pallas_fused",
    ),
]


@pytest.mark.parametrize("backend,channel_grid", _REFUSED)
def test_pallas_kernels_compile_for_one_chip(
    one_chip, monkeypatch, backend, channel_grid
):
    """The paper's n=4096/t=6/v=30 point on each Pallas datapath,
    lowered through Mosaic (``interpret=False``) for one v5e."""
    pl = repro.plan(
        n=4096, t=6, v=30, backend=backend, channel_grid=channel_grid
    )
    monkeypatch.setattr(ops, "_is_tpu", lambda: True)  # compiled mode
    try:
        compiled = _compile_polymul(pl, one_chip, rows=8)
    except PlanError as e:  # a ValueError, but not the compiler's refusal
        pytest.fail(f"refused before Mosaic saw the kernel: {e}")
    assert "tpu_custom_call" in compiled.as_text()
