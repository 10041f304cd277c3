"""The main path's kernels compile for a TPU v5e, at real widths.

The chip is described (``v5e:2x2``), not attached: the TPU compiler that
ships with libtpu compiles for it here and refuses what the chip would
refuse (scoped VMEM, tiling alignment, HBM), which interpret-mode and
CPU-backend tests cannot show. Nothing runs, so these tests say nothing
about results or times.

The topology is described inside a module fixture, never at import time:
only one process at a time may load libtpu, and pytest-xdist workers each
import every test file. Keep every such compile in this one file so they
all land on the one worker that loads the library.
"""

import numpy as np
import pytest

ROWS = 1024  # secret feed batch on the pallas path (PALLAS_BATCH)
CHUNK = 8192  # row width on the pallas path (PALLAS_CHUNK_LEN)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip would be written to the persistent
    cache and could not be read back without the chip."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for(one_chip, no_persistent_cache):
    """``compile_for(fn, *shapes)`` -> HLO text of ``fn`` compiled for
    one described chip; ``shapes`` are ``(shape, dtype)`` pairs."""
    import jax

    def run(fn, *shapes):
        args = [
            jax.ShapeDtypeStruct(s, np.dtype(d), sharding=one_chip)
            for s, d in shapes
        ]
        jitted = getattr(fn, "__wrapped__", fn)
        if not hasattr(jitted, "lower"):
            jitted = jax.jit(jitted)
        return jitted.lower(*args).compile().as_text()

    return run


def _rules(n: int | None):
    from trivy_tpu.secret.device_compile import compile_rules
    from trivy_tpu.secret.rules import builtin_rules

    rules = builtin_rules()
    return compile_rules(rules[:n] if n else rules)


@pytest.mark.parametrize(
    "n_rules",
    [4, pytest.param(None, marks=pytest.mark.slow, id="all-builtin")],
)
def test_match_kernel(compile_for, n_rules):
    from trivy_tpu.ops.match_pallas import build_match_fn_pallas

    fn = build_match_fn_pallas(_rules(n_rules), CHUNK, include_keywords=False)
    assert "tpu_custom_call" in compile_for(fn, ((ROWS, CHUNK), "uint8"))


def test_prefilter_kernel(compile_for):
    from trivy_tpu.ops.prefilter import build_prefilter_fn

    fn = build_prefilter_fn(_rules(4), CHUNK, backend="pallas")
    assert "tpu_custom_call" in compile_for(fn, ((ROWS, CHUNK), "uint8"))


def test_decompress_stage(compile_for):
    from trivy_tpu.ops.decompress import build_decompress_fn
    from trivy_tpu.secret.compress import COMPRESS_MIN_RATIO, SlabCodec

    codec = SlabCodec(CHUNK)
    fn = build_decompress_fn(CHUNK, codec.tab_bytes, codec.tab_len)
    # the top wire rung of the full bucket, as TpuSecretScanner sizes it
    wire = -(-int(ROWS * CHUNK * COMPRESS_MIN_RATIO) // 128) * 128
    hlo = compile_for(
        fn, ((wire,), "uint8"), ((ROWS,), "int32"), ((ROWS,), "int32"),
        ((ROWS,), "uint8"),
    )
    assert "ENTRY" in hlo


def test_license_bytes_scorer(compile_for):
    from trivy_tpu.licensing.classify import LicenseClassifier
    from trivy_tpu.ops import ngram_score as ng

    table = LicenseClassifier(backend="device").corpus_table()
    width = 8192
    rows = max(8, ng.BYTES_ROW_ELEMS // width)  # DeviceBytesScorer's rung
    gate = ng.build_bytes_gate_fn(width, table.lut)
    assert "ENTRY" in compile_for(
        gate, ((rows, width), "uint8"),
        (table.gate.bloom8.shape, table.gate.bloom8.dtype),
        (table.gate.bloom4.shape, table.gate.bloom4.dtype),
    )
    score = ng.build_bytes_score_fn(
        width, width // 4, table.lic_per_shard, table.lut,
        table.p1, table.p2, table.hash_p, table.ngram,
    )
    assert "ENTRY" in compile_for(
        score, ((rows, width), "uint8"),
        (table.keys.shape, table.keys.dtype),
        (table.credit.shape, table.credit.dtype),
    )


def test_cve_check_ops_gather(compile_for):
    from trivy_tpu.ops.verscmp import check_ops_gather

    rows = 1 << 16  # a check_ops_gather_bucketed row bucket
    hlo = compile_for(
        check_ops_gather, ((1024, 16), "int32"), ((131072, 16), "int32"),
        ((rows,), "int32"), ((rows,), "int32"), ((rows,), "int32"),
    )
    assert "ENTRY" in hlo
