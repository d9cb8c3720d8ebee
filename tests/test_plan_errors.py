"""Message-pinning tests: every PlanError branch in the plan compiler.

tests/test_plan.py::test_validation_errors checks the common rejections
with loose matches; this suite pins the *message text* of every raise
branch so an error-path refactor cannot silently swap, merge, or
degrade a diagnostic. The analyzer's planlint assumes validate_plan is
the structural gate — these tests are what make that assumption safe.
"""

import jax.numpy as jnp
import pytest

from repro.core import plan as plan_lib
from repro.core import plan_compiler
from repro.core import schema as schema_lib
from repro.core.plan import ColumnSpec, PreprocPlan, op

SMALL = schema_lib.TableSchema(n_dense=4, n_sparse=5, vocab_range=101)
PlanError = plan_compiler.PlanError


def _validate(cols):
    plan_compiler.validate_plan(PreprocPlan(tuple(cols)), SMALL)


def sparse(ops, source=0, name=""):
    return ColumnSpec(kind="sparse", source=source, ops=tuple(ops), name=name)


def dense(ops, source=0, name=""):
    return ColumnSpec(kind="dense", source=source, ops=tuple(ops), name=name)


def test_empty_plan():
    with pytest.raises(PlanError, match=r"^plan has no columns$"):
        _validate([])


def test_duplicate_column_names():
    col = dense([op("Neg2Zero")], name="x")
    with pytest.raises(PlanError, match=r"^duplicate column names in plan$"):
        _validate([col, dense([op("Neg2Zero")], source=1, name="x")])


def test_unknown_column_kind():
    bad = ColumnSpec(kind="ragged", source=0, ops=(op("Neg2Zero"),))
    with pytest.raises(PlanError, match=r"unknown column kind 'ragged'"):
        _validate([bad])


def test_unknown_source_index():
    with pytest.raises(
        PlanError,
        match=r"unknown column — source 99 not in the schema's 5 sparse",
    ):
        _validate([sparse(plan_lib.SPARSE_CANONICAL, source=99)])
    with pytest.raises(
        PlanError,
        match=r"unknown column — source -1 not in the schema's 4 dense",
    ):
        _validate([dense([op("Neg2Zero")], source=-1)])


def test_unknown_op():
    with pytest.raises(PlanError, match=r"unknown op 'Sqrt'"):
        _validate([dense([op("Sqrt")])])


def test_domain_mismatch():
    with pytest.raises(
        PlanError, match=r"op Modulus applies to sparse columns, not dense"
    ):
        _validate([dense([op("Modulus")])])
    with pytest.raises(
        PlanError, match=r"op Logarithm applies to dense columns, not sparse"
    ):
        _validate([sparse([op("Logarithm")])])


def test_unknown_param():
    with pytest.raises(PlanError, match=r"op Neg2Zero has no param 'gain'"):
        _validate([dense([op("Neg2Zero", gain=2)])])


def test_decode_stage_op_after_compute():
    with pytest.raises(
        PlanError,
        match=r"decode-stage op FillMissing must precede compute ops",
    ):
        _validate([sparse([op("Modulus"), op("FillMissing")])])


def test_hashcross_not_first():
    with pytest.raises(
        PlanError, match=r"HashCross must be the first compute op"
    ):
        _validate([sparse([op("Modulus"), op("HashCross")], source=(0, 1))])


def test_hashcross_needs_pair_source():
    with pytest.raises(
        PlanError, match=r"HashCross needs a \(a, b\) pair source, got 0"
    ):
        _validate([sparse([op("HashCross"), op("Modulus")])])


def test_vocab_op_repeated():
    with pytest.raises(PlanError, match=r"op Modulus appears twice"):
        _validate([sparse([op("Modulus"), op("Modulus")])])
    with pytest.raises(PlanError, match=r"op GenVocab appears twice"):
        _validate([sparse([op("Modulus"), op("GenVocab"), op("GenVocab")])])


def test_genvocab_requires_modulus():
    with pytest.raises(
        PlanError, match=r"GenVocab requires a preceding Modulus"
    ):
        _validate([sparse([op("GenVocab")])])


def test_applyvocab_requires_genvocab():
    with pytest.raises(
        PlanError, match=r"ApplyVocab requires a preceding GenVocab"
    ):
        _validate([sparse([op("Modulus"), op("ApplyVocab")])])


def test_modulus_range_not_positive_int():
    with pytest.raises(
        PlanError, match=r"Modulus range must be a positive int"
    ):
        _validate([sparse([op("Modulus", range=0)])])
    with pytest.raises(
        PlanError, match=r"Modulus range must be a positive int"
    ):
        _validate([sparse([op("Modulus", range=2.5)])])


def test_clip_and_minmax_need_ordered_bounds():
    with pytest.raises(PlanError, match=r"Clip needs params lo < hi"):
        _validate([dense([op("Clip", lo=5.0, hi=1.0)])])
    with pytest.raises(PlanError, match=r"MinMaxScale needs params lo < hi"):
        _validate([dense([op("MinMaxScale", lo=0.0)])])


def test_bucketize_boundaries():
    msg = r"Bucketize boundaries must be a non-empty strictly-increasing"
    with pytest.raises(PlanError, match=msg):
        _validate([dense([op("Bucketize", boundaries=())])])
    with pytest.raises(PlanError, match=msg):
        _validate([dense([op("Bucketize", boundaries=(3.0, 1.0))])])
    with pytest.raises(PlanError, match=msg):
        _validate([dense([op("Bucketize", boundaries=(1.0, 1.0))])])


def test_pair_source_needs_hashcross():
    with pytest.raises(
        PlanError, match=r"a pair source needs a HashCross op to combine it"
    ):
        _validate([sparse([op("Modulus")], source=(0, 1))])


def test_vocab_ranges_may_differ():
    mk = lambda src, rng: sparse(
        [op("Modulus", range=rng), op("GenVocab"), op("ApplyVocab")],
        source=src,
    )
    _validate([mk(0, 7), mk(1, 8)])  # one flat state, an offset per column
    compiled = plan_compiler.compile_plan(
        PreprocPlan((mk(0, 7), mk(1, 8))), SMALL, fused=False
    )
    assert compiled.vocab_ranges == (7, 8)
    assert compiled.vocab_layout.offsets == (0, 128)


@pytest.mark.parametrize("rng", [0, -1])
def test_vocab_range_must_be_positive(rng):
    with pytest.raises(
        PlanError, match=r"^sparse:0: Modulus range must be a positive int$"
    ):
        _validate([
            sparse([op("Modulus", range=rng), op("GenVocab"), op("ApplyVocab")])
        ])


# -- the two compiler branches only reachable by direct call ----------- #
def _compiled():
    return plan_compiler.compile_plan(
        plan_lib.criteo_default(SMALL), SMALL, fused=False
    )


def test_eval_sparse_unhandled_op():
    compiled = _compiled()
    raw = jnp.zeros((4, 1), jnp.int32)
    with pytest.raises(
        PlanError, match=r"^unhandled sparse op ApplyVocab in compiler$"
    ):
        compiled._eval_sparse(raw, (op("ApplyVocab"),), (0,))


def test_eval_dense_unhandled_op():
    compiled = _compiled()
    raw = jnp.zeros((4, 1), jnp.int32)
    with pytest.raises(
        PlanError, match=r"^unhandled dense op Hex2Int in compiler$"
    ):
        compiled._eval_dense(raw, (op("Hex2Int"),))
