"""Compile the resident placement's programs for a described TPU v5e.

Nothing runs and no chip is needed: the TPU compiler refuses here what the
chip would refuse (VMEM, tiling, HBM), and ``memory_analysis`` gives the
bytes each program needs at the configurations' own field shapes.  The
topology is described only inside the fixture, never at import.

    JAX_PLATFORMS=cpu python3 -m pytest -q -s bench/tests/test_tpu_compile.py
"""

from __future__ import annotations

import json
import os

import jax
import pytest

from bench import codec, manifest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_AUTOTUNE"] = "0"
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("config", ["hurr-quant-u16", "hurr-field-f32"])
def test_device_programs_compile_for_v5e(config, one_chip, monkeypatch):
    from repro.core import format as fmt

    # the kernels pick their Mosaic path by the default backend's name
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = json.loads((manifest.BENCH / "configs" / f"{config}.json").read_text())
    compress, decompress = codec.device_programs(
        cfg, "fused-mono", "fused-mono"
    )
    dtype = jax.numpy.dtype(cfg["dtype"])
    field = jax.ShapeDtypeStruct(tuple(cfg["field_shape"]), dtype,
                                 sharding=one_chip)
    n = codec.field_bytes(cfg)
    cap = fmt.max_compressed_bytes(n, cfg["symbol_size"], cfg["chunk_symbols"])
    blob = jax.ShapeDtypeStruct((cap,), jax.numpy.uint8, sharding=one_chip)
    for name, fn, arg in (("compress", compress, field),
                          ("decompress", decompress, blob)):
        compiled = fn.lower(arg).compile()
        assert "tpu_custom_call" in compiled.as_text()
        mem = compiled.memory_analysis()
        print(f"{config} {name}: args {mem.argument_size_in_bytes} "
              f"out {mem.output_size_in_bytes} temp {mem.temp_size_in_bytes}")
