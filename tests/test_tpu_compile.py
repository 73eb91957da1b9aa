"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

Interpret-mode parity cannot see what the chip's compiler refuses
(scatters, value-level dynamic slices, block shapes off the (8, 128)
rule, scoped-VMEM overruns), so every wavefront mode is compiled here for
one chip of a described ``v5e:2x2`` at real widths: one band at the
paper's l = 20, several bands at l = 128, and a long window whose band
depth the registry's VMEM model picks.  Nothing runs; a compile that
passes is not a chip run.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Keep these tests in this one file.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import registry

MODES4 = ["dtw", "erp", "frechet", "levenshtein"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            jax.config.update("jax_enable_compilation_cache", prev)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(one_chip, name, B, Lx, Ly, d, **kw) -> str:
    """HLO text of ``device_call`` compiled for the described chip."""
    tokens = name == "levenshtein"
    dt = jnp.int32 if tokens else jnp.float32
    xs = (B, Lx) if tokens else (B, Lx, d)
    ys = (B, Ly) if tokens else (B, Ly, d)
    args = [jax.ShapeDtypeStruct(xs, dt, sharding=one_chip),
            jax.ShapeDtypeStruct(ys, dt, sharding=one_chip),
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((B,), jnp.float32, sharding=one_chip)]
    spec = registry.get(name)
    # interpret=False: this process runs on the CPU, whose policy would
    # interpret; the described chip compiles the kernel
    fn = jax.jit(lambda *a: spec.device_call(*a, interpret=False, **kw))
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("name", MODES4)
def test_wavefront_single_band_compiles(one_chip, name):
    hlo = _compile(one_chip, name, 256, 20, 20, 2, exec="pallas")
    assert registry.default_tile(20, 20, 2) == 40   # one band
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("name", MODES4)
def test_wavefront_multi_band_compiles(one_chip, name):
    hlo = _compile(one_chip, name, 256, 128, 128, 2, exec="pallas", tile=32)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("name", ["dtw", "erp"])
def test_wavefront_long_window_default_tile_fits_vmem(one_chip, name):
    # the padded VMEM model must pick a band depth the chip accepts; ERP
    # carries the extra gap channel and the largest in-loop temporaries
    tile = registry.default_tile(512, 512, 1)
    assert 8 <= tile < 1024
    hlo = _compile(one_chip, name, 256, 512, 512, 1, exec="pallas")
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("name", MODES4)
def test_scan_twin_compiles(one_chip, name):
    hlo = _compile(one_chip, name, 256, 20, 20, 2, exec="scan")
    assert "tpu_custom_call" not in hlo


@pytest.mark.parametrize("name", ["lb:dtw", "lb:erp", "lb:frechet"])
def test_envelope_specs_compile(one_chip, name):
    # pure elementwise jnp: XLA fuses it, no Pallas kernel expected
    hlo = _compile(one_chip, name, 256, 20, 20, 2)
    assert "tpu_custom_call" not in hlo


@pytest.mark.parametrize("exec_mode", ["pallas", "scan"])
def test_band_mode_compiles_at_the_match_cell(one_chip, exec_mode):
    # protein_match: lam 40, l 20, lambda0 2; a start-pair row is
    # lam + l = 60 database residues against 62 query residues, and the
    # cell's largest verify class is its traffic file's verify_warm_rows
    B = 65536
    hlo = _compile(one_chip, "levenshtein", B, 60, 62, 1, exec=exec_mode,
                   band=(40, 2))
    assert ("wavefront_lev_band" in hlo) == (exec_mode == "pallas")


def test_wide_block_compiles_at_the_filter_class(one_chip):
    # a merged round of protein_match segment rows (22 residues against
    # windows of 20) at registry.MAX_BATCH rows takes the wide block
    B = registry.MAX_BATCH
    block = registry.default_block(B)
    assert block == registry.WIDE_BLOCK
    hlo = _compile(one_chip, "levenshtein", B, 22, 20, 1, exec="pallas",
                   block_b=block)
    assert "wavefront_lev" in hlo
