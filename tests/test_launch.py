"""Launch-layer units that run in-process (the 512-device dry-run itself
is exercised out-of-band; its artifacts are validated here if present)."""

import glob
import json
import os

import pytest

from repro import configs
from repro.configs import shapes as shapes_lib
from repro.hw import TPU_V5E, chip_spec, roofline_terms
from repro.launch.mesh import data_axes

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")


def test_shape_applicability_matrix():
    rows = 0
    for arch in configs.ARCH_IDS:
        cfg = configs.get(arch)
        for shape in shapes_lib.ALL_SHAPES:
            ok, reason = shapes_lib.applicable(cfg, shape)
            rows += 1
            if shape.name == "long_500k":
                assert ok == cfg.sub_quadratic, (arch, reason)
            else:
                assert ok
    assert rows == 40  # the full assigned cell matrix


def test_roofline_terms_math():
    t = roofline_terms(197e12, 819e9, 50e9, n_chips=1, chip=TPU_V5E)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["collective_s"] == pytest.approx(1.0)


def test_chip_spec_keyed_by_device_kind():
    assert chip_spec("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="no peak rates"):
        chip_spec("cpu")


def test_mesh_factory_shapes():
    # NOTE: runs with 1 device — only validates the arithmetic helpers
    import repro.launch.mesh as mesh_lib

    assert data_axes.__name__ == "data_axes"
    # production shapes are fixed by the brief
    assert mesh_lib.make_production_mesh.__doc__.startswith("16×16")


@pytest.mark.skipif(
    not glob.glob(os.path.join(ART_DIR, "*.json")),
    reason="dry-run artifacts not present",
)
def test_dryrun_artifacts_validity():
    """Every recorded cell: status ok/skip; ok cells carry the full
    measurement payload; no cell errored."""
    bad = []
    for path in glob.glob(os.path.join(ART_DIR, "*.json")):
        r = json.load(open(path))
        if r["status"] == "error":
            bad.append((os.path.basename(path), r.get("error", "")[:80]))
            continue
        if r["status"] == "ok" and "cost_extrapolated" in r:
            ce = r["cost_extrapolated"]
            assert ce["flops"] > 0, path
            assert ce["bytes"] > 0, path
            assert r["mem"]["temp_bytes"] >= 0, path
    assert not bad, bad
