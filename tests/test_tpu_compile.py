"""The default TPU route compiles for a TPU v5e, without a chip.

Interpret mode on the CPU cannot show what Mosaic refuses (tiling of
blocks, dynamic indexing, scoped VMEM). These tests compile, for a
described ``v5e:2x2`` topology and at the engine's real widths (26 sparse
+ 13 dense columns, 16384-row chunks), every Pallas kernel that a
default ``PipelineConfig`` routes to on a TPU at the 5K and 1M vocabulary
points, and pin the route labels the plan compiler gives there. They
steer the route to the TPU by patching ``repro.kernels.on_tpu``, since
JAX itself still reports the CPU backend here. A compile is not a chip
run: nothing here times or checks results (``chip_smoke.py`` does).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels as kernels_lib
from repro.core import pipeline as P
from repro.core import schema as schema_lib
from repro.core import vocab as vocab_lib
from repro.kernels.fused_vocab import kernel as fv_kernel
from repro.kernels.fused_xform import kernel as fx_kernel

ROWS = 1 << 14  # PipelineConfig.max_rows_per_chunk
N_SPARSE, N_DENSE = schema_lib.CRITEO.n_sparse, schema_lib.CRITEO.n_dense


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_route(monkeypatch):
    """Route and interpret decisions as on a TPU backend."""
    monkeypatch.setattr(kernels_lib, "on_tpu", lambda backend=None: True)


def _compile(fn, *args):
    """Compile for the described chip; returns the optimized HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(one_chip, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# MLPerf DLRM's per-column sizes on Criteo 1TB (--num_embeddings_per_feature)
MLPERF_40M = (
    40000000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63, 40000000, 3067956,
    405282, 10, 2209, 11938, 155, 4, 976, 14, 40000000, 40000000, 40000000,
    590152, 12973, 108, 36,
)


def _flat(one_chip, lay):
    return _spec(one_chip, (lay.entries,))


def test_genvocab_vmem_tier_compiles_5k(one_chip):
    """Loop ①, 5K: the whole flat state resident in VMEM."""
    lay = vocab_lib.layout((5000,) * N_SPARSE)
    hlo = _compile(
        lambda st, sp, pos: fv_kernel.fused_genvocab(
            st, sp, pos, ranges=lay.ranges, offsets=lay.offsets,
            row_block=1024, interpret=False,
        ),
        _flat(one_chip, lay),
        _spec(one_chip, (ROWS, N_SPARSE)),
        _spec(one_chip, (ROWS,)),
    )
    assert "tpu_custom_call" in hlo


def _compile_slabs(one_chip, ranges, planes, slab_range=None):
    """Compile the slab kernel for columns of ``ranges`` with one or two
    carried planes; returns the optimized HLO text."""
    lay = vocab_lib.layout(ranges, track_counts=planes == 2, slab_range=slab_range)
    state = _flat(one_chip, lay)
    counts = state if planes == 2 else None
    return _compile(
        lambda st, ct, sp, pos: fv_kernel.fused_genvocab_slabs(
            st, ct, sp, pos, ranges=lay.ranges, offsets=lay.offsets,
            slab=lay.slab_entries, row_block=1024, interpret=False,
        ),
        state,
        counts,
        _spec(one_chip, (ROWS, N_SPARSE)),
        _spec(one_chip, (ROWS,)),
    )


@pytest.mark.parametrize("planes", [1, 2], ids=["first_pos", "with_counts"])
def test_genvocab_slab_tier_compiles_1m(one_chip, planes):
    """Loop ①, 1M: the 26 × 1M flat state streamed through VMEM slab by
    slab (with the optional count plane too: two carried planes)."""
    assert "tpu_custom_call" in _compile_slabs(one_chip, (1_000_000,) * N_SPARSE, planes)


@pytest.mark.parametrize("planes", [1, 2], ids=["first_pos", "with_counts"])
def test_genvocab_slab_tier_compiles_10m(one_chip, planes):
    """Loop ①, 10M: past the packed int32 sort key's range, so each
    tile's values are sorted with their rows as a second operand."""
    assert fv_kernel.key_row_bits(10_000_000, 1024) is None
    assert "tpu_custom_call" in _compile_slabs(one_chip, (10_000_000,) * N_SPARSE, planes)


def test_genvocab_slab_tier_compiles_mlperf_40m(one_chip):
    """Loop ①, Criteo 1TB at MLPerf's per-column sizes: 26 ranges from 3
    to 40M in one 817 MB flat state (195 slabs), the pre-pass in its
    two-operand sort form."""
    lay = vocab_lib.layout(MLPERF_40M)
    assert fv_kernel.key_row_bits(max(MLPERF_40M), 1024) is None
    assert lay.n_slabs == 195 and lay.entries <= 1.01 * sum(MLPERF_40M)
    assert "tpu_custom_call" in _compile_slabs(one_chip, MLPERF_40M, planes=1)


def test_genvocab_one_slab_compiles_5k_counts(one_chip):
    """Loop ①, 5K with occurrence counts: the vmem tier runs the slab
    kernel with one resident slab (the run is the whole tile)."""
    assert vocab_lib.layout((5000,) * N_SPARSE, track_counts=True).n_slabs == 1
    hlo = _compile_slabs(one_chip, (5000,) * N_SPARSE, planes=2)
    assert "tpu_custom_call" in hlo


def test_mod_dense_hbm_tier_compiles_1m(one_chip):
    """Loop ②, 1M: modulus (plus each column's offset) and the dense
    transform fused, gather in XLA."""
    lay = vocab_lib.layout((1_000_000,) * N_SPARSE)
    hlo = _compile(
        lambda sp, d: fx_kernel.fused_mod_dense(
            sp, d, ranges=lay.ranges, offsets=lay.offsets, row_block=256,
            interpret=False,
        ),
        _spec(one_chip, (ROWS, N_SPARSE)),
        _spec(one_chip, (ROWS, N_DENSE)),
    )
    assert "tpu_custom_call" in hlo


def test_vmem_transform_kernel_still_refused(one_chip):
    """Why loop ②'s VMEM tier is off the TPU route: Mosaic refuses the
    in-kernel take_along_axis gather. When this starts to compile, the
    route can come back (plan_compiler, ROADMAP speed item 2)."""
    with pytest.raises(Exception):
        lay = vocab_lib.layout((5000,) * N_SPARSE)
        _compile(
            lambda t, sp, d: fx_kernel.fused_transform(
                t, sp, d, ranges=lay.ranges, offsets=lay.offsets,
                row_block=256, interpret=False,
            ),
            _flat(one_chip, lay),
            _spec(one_chip, (ROWS, N_SPARSE)),
            _spec(one_chip, (ROWS, N_DENSE)),
        )


@pytest.mark.parametrize(
    "schema,routes,kernels",
    [
        (schema_lib.CRITEO, ("fused/vmem", "unfused"), (1, 0)),
        (schema_lib.CRITEO_1M, ("fused/hbm_slab", "fused/hbm"), (1, 1)),
        (
            schema_lib.TableSchema(n_dense=N_DENSE, n_sparse=N_SPARSE, vocab_range=MLPERF_40M),
            ("fused/hbm_slab", "fused/hbm"),
            (1, 1),
        ),
    ],
    ids=["5k", "1m", "mlperf-40m"],
)
def test_default_config_route_compiles(one_chip, tpu_route, schema, routes, kernels):
    """A default PipelineConfig on a TPU: its route labels, and both
    compiled-plan halves (loop ① state update, loop ② transform) on a
    decoded 16384-row chunk compile with the kernels the route names."""
    pipe = P.PiperPipeline(P.PipelineConfig(schema=schema))
    compiled = pipe.compiled
    assert (compiled.vocab_route, compiled.xform_route) == routes
    batch = schema_lib.TabularBatch(
        label=_spec(one_chip, (ROWS,)),
        dense=_spec(one_chip, (ROWS, N_DENSE)),
        sparse=_spec(one_chip, (ROWS, N_SPARSE)),
        valid=_spec(one_chip, (ROWS,), jnp.bool_),
    )
    state = jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype), jax.eval_shape(pipe.init_state)
    )
    lay = compiled.vocab_layout
    assert lay.ranges == schema.ranges
    vocabulary = vocab_lib.Vocabulary(
        table=_flat(one_chip, lay), sizes=_spec(one_chip, (N_SPARSE,)), layout=lay
    )
    loop1 = _compile(compiled.vocab_step, state, batch)
    loop2 = _compile(compiled.transform, vocabulary, batch)
    assert loop1.count("tpu_custom_call") == kernels[0]
    assert loop2.count("tpu_custom_call") == kernels[1]
