"""Compile the mining kernels for a described TPU v5e, without a chip.

The TPU compiler is installed next to the CPU backend, so Mosaic can be
asked here whether it accepts each kernel at the widths ``chip_smoke.py``
runs on the chip: 200 sites x 5,000 transactions x 3 words against 1,024
candidates, and 25,000 points (lane-padded to 128) against 20 centres
(padded to 128).  Interpret mode accepts layouts Mosaic refuses, so the
CPU property tests alone do not show that a kernel compiles.

Nothing runs and nothing is timed: each test lowers and compiles one
program and asserts that it holds a ``tpu_custom_call`` (a Mosaic kernel,
not the interpreter).  The topology is described inside a fixture, never
at import time, so only the worker that runs this file loads the TPU
library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, ops

N_SITES, N_TX, N_WORDS, N_CAND = 200, 5_000, 3, 1024
N_PTS, DIM, K = 25_000, 8, 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch, one_chip, no_compile_cache):
    """Steer the ops wrappers onto their TPU branch (``interpret=False``)
    and return a compile function for arguments of the given shapes."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    def compile_text(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    return compile_text


TX = ((N_TX, N_WORDS), jnp.uint32)
MASKS = ((N_CAND, N_WORDS), jnp.uint32)
TX_S = ((N_SITES, N_TX, N_WORDS), jnp.uint32)
MASKS_S = ((N_SITES, N_CAND, N_WORDS), jnp.uint32)
PTS = ((N_PTS, DIM), jnp.float32)
CENTERS = ((K, DIM), jnp.float32)
PTS_S = ((N_SITES, N_PTS, DIM), jnp.float32)
CENTERS_S = ((N_SITES, K, DIM), jnp.float32)

# entry point -> (call with a block config, argument shapes)
SUPPORT_FORMS = {
    "support_count": (lambda b: lambda t, m: ops.support_count(t, m, block=b), (TX, MASKS)),
    "support_count_prune": (
        lambda b: lambda t, m, c: ops.support_count_prune(t, m, c, block=b),
        (TX, MASKS, ((), jnp.int32)),
    ),
    "support_count_sites": (
        lambda b: lambda t, m: ops.support_count_sites(t, m, block=b),
        (TX_S, MASKS_S),
    ),
    "support_count_prune_sites": (
        lambda b: lambda t, m, c: ops.support_count_prune_sites(t, m, c, block=b),
        (TX_S, MASKS_S, ((N_SITES,), jnp.int32)),
    ),
}
KMEANS_FORMS = {
    "kmeans_assign": (lambda b: lambda x, c: ops.kmeans_assign(x, c, block=b), (PTS, CENTERS)),
    "kmeans_assign_sites": (
        lambda b: lambda x, c: ops.kmeans_assign_sites(x, c, block=b),
        (PTS_S, CENTERS_S),
    ),
}

# every block the autotuner may pick at these shapes
SUPPORT_LATTICE = autotune.support_count_candidates(N_WORDS, N_TX, N_CAND, smoke=False)
KMEANS_LATTICE = autotune.kmeans_assign_candidates(N_PTS, 128, 128, smoke=False)


@pytest.mark.parametrize("form", sorted({**SUPPORT_FORMS, **KMEANS_FORMS}))
def test_default_blocks_compile_to_mosaic(mosaic, form):
    make, shapes = {**SUPPORT_FORMS, **KMEANS_FORMS}[form]
    assert "tpu_custom_call" in mosaic(make(None), *shapes)


@pytest.mark.parametrize("block", SUPPORT_LATTICE, ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("form", sorted(SUPPORT_FORMS))
def test_support_lattice_compiles_to_mosaic(mosaic, form, block):
    make, shapes = SUPPORT_FORMS[form]
    assert "tpu_custom_call" in mosaic(make(block), *shapes)


@pytest.mark.parametrize("block", KMEANS_LATTICE, ids=str)
@pytest.mark.parametrize("form", sorted(KMEANS_FORMS))
def test_kmeans_lattice_compiles_to_mosaic(mosaic, form, block):
    make, shapes = KMEANS_FORMS[form]
    assert "tpu_custom_call" in mosaic(make(block), *shapes)


def test_lattice_covers_every_block_size():
    """The shapes above are wide enough that no lattice point is filtered
    out, so the tests above compile the whole lattice."""
    sizes = set(autotune._LATTICE)
    assert {b for cfg in SUPPORT_LATTICE for b in cfg} == sizes
    assert set(KMEANS_LATTICE) == sizes
