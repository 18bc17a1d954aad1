"""BENCHMARK.json and the files it names: every name finds its file, and
the reference matrices are the solver's at small sizes."""
import json
import os
import re

import numpy as np
import pytest

import harness

SPEC = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_finds_its_files():
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert os.path.exists(os.path.join(
            harness.BENCH, "loops", cell.traffic["loop"] + ".py"))
        assert os.path.exists(os.path.join(
            harness.BENCH, "references", cell.config["family"] + ".py"))
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"])
        assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                           m["name"] + ".py"))
    names = [m["name"] for m in SPEC["end_to_end"]]
    for m in SPEC["per_layer"]:
        assert m["moves"] in names


def test_peaks_cover_the_chip():
    peaks = harness.read_json(os.path.join(harness.BENCH, "peaks.json"))
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("family, params", [
    ("SpinChainXXZ", {"n_sites": 10, "n_up": 5, "Jxy": 1.0, "Jz": 1.0}),
    ("SpinChainXXZ", {"n_sites": 12, "n_up": 5, "Jxy": 0.7, "Jz": 1.3}),
])
def test_reference_matrix_is_the_family(family, params):
    from repro.matrices import get_family

    ref = harness.load_module(os.path.join(
        harness.BENCH, "references", family + ".py")).build_csr(**params)
    mine = get_family(family, **params).build_csr().to_dense()
    assert ref.shape == mine.shape
    # the diagonal sums its bond terms in another order: last-bit rounding
    np.testing.assert_allclose(ref.toarray(), mine, rtol=0, atol=1e-15)


@pytest.mark.parametrize("config", ["spinchain22"])
def test_configuration_counts(config):
    """D and nnz as the configuration states them, from the reference."""
    cfg = harness.read_json(os.path.join(harness.BENCH, "configs",
                                         config + ".json"))
    ref = harness.load_module(os.path.join(
        harness.BENCH, "references", cfg["family"] + ".py")).build_csr(
        **cfg["params"])
    assert ref.shape == (cfg["D"], cfg["D"]) and ref.nnz == cfg["nnz"]
    assert json.dumps(cfg)
