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
from repro.kernels.fused_vocab import ops as fv_ops
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


def test_genvocab_vmem_tier_compiles_5k(one_chip):
    """Loop ①, 5K: the whole state resident in VMEM."""
    hlo = _compile(
        lambda st, sp, pos: fv_kernel.fused_genvocab(
            st, sp, pos, row_block=1024, interpret=False
        ),
        _spec(one_chip, (N_SPARSE, 5000)),
        _spec(one_chip, (ROWS, N_SPARSE)),
        _spec(one_chip, (ROWS,)),
    )
    assert "tpu_custom_call" in hlo


def _compile_slabs(one_chip, vocab_range, planes, slab_range=None):
    """Compile the slab kernel for ``[26, vocab_range]`` with one or two
    carried planes; returns the optimized HLO text."""
    sr = slab_range or fv_ops.default_slab_range(
        N_SPARSE, vocab_range, track_counts=planes == 2
    )
    width = -(-vocab_range // sr) * sr
    state = _spec(one_chip, (N_SPARSE, width))
    counts = state if planes == 2 else None
    return _compile(
        lambda st, ct, sp, pos: fv_kernel.fused_genvocab_slabs(
            st, ct, sp, pos, slab_range=sr, vocab_range=vocab_range,
            row_block=1024, interpret=False,
        ),
        state,
        counts,
        _spec(one_chip, (ROWS, N_SPARSE)),
        _spec(one_chip, (ROWS,)),
    )


@pytest.mark.parametrize("planes", [1, 2], ids=["first_pos", "with_counts"])
def test_genvocab_slab_tier_compiles_1m(one_chip, planes):
    """Loop ①, 1M: [26, 1M] state streamed through VMEM slab by slab
    (with the optional count plane too: two carried planes)."""
    assert "tpu_custom_call" in _compile_slabs(one_chip, 1_000_000, planes)


@pytest.mark.parametrize("planes", [1, 2], ids=["first_pos", "with_counts"])
def test_genvocab_slab_tier_compiles_10m(one_chip, planes):
    """Loop ①, 10M: past the packed int32 key's range, so each tile's
    values are sorted with their positions as a second operand and the
    positions arrive as a whole entry tile in SMEM."""
    sr = fv_ops.default_slab_range(N_SPARSE, 10_000_000, track_counts=planes == 2)
    assert fv_kernel.key_row_bits(-(-10_000_000 // sr) * sr, 1024) is None
    assert "tpu_custom_call" in _compile_slabs(one_chip, 10_000_000, planes)


def test_genvocab_one_slab_compiles_5k_counts(one_chip):
    """Loop ①, 5K with occurrence counts: the vmem tier runs the slab
    kernel with one resident slab (each column's run the whole column)."""
    width = -(-5000 // fv_kernel.LANES) * fv_kernel.LANES
    hlo = _compile_slabs(one_chip, 5000, planes=2, slab_range=width)
    assert "tpu_custom_call" in hlo


def test_mod_dense_hbm_tier_compiles_1m(one_chip):
    """Loop ②, 1M: modulus + dense transform fused, gather in XLA."""
    hlo = _compile(
        lambda sp, d: fx_kernel.fused_mod_dense(
            sp, d, vocab_range=1_000_000, row_block=256, interpret=False
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
        _compile(
            lambda t, sp, d: fx_kernel.fused_transform(
                t, sp, d, row_block=256, interpret=False
            ),
            _spec(one_chip, (N_SPARSE, 5000)),
            _spec(one_chip, (ROWS, N_SPARSE)),
            _spec(one_chip, (ROWS, N_DENSE)),
        )


@pytest.mark.parametrize(
    "schema,routes,kernels",
    [
        (schema_lib.CRITEO, ("fused/vmem", "unfused"), (1, 0)),
        (schema_lib.CRITEO_1M, ("fused/hbm_slab", "fused/hbm"), (1, 1)),
    ],
    ids=["5k", "1m"],
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
    vocabulary = vocab_lib.Vocabulary(
        table=_spec(one_chip, (N_SPARSE, schema.vocab_range)),
        sizes=_spec(one_chip, (N_SPARSE,)),
    )
    loop1 = _compile(compiled.vocab_step, state, batch)
    loop2 = _compile(compiled.transform, vocabulary, batch)
    assert loop1.count("tpu_custom_call") == kernels[0]
    assert loop2.count("tpu_custom_call") == kernels[1]
