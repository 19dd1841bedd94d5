import json

import pytest

from bench import work


@pytest.mark.parametrize("n,t,S,L,cascade,crt", [
    (4096, 6, 6, 7, 2_555_904, 4_849_664),
    (8192, 7, 7, 8, 5_963_776, 11_272_192),
])
def test_bytes_of_one_batch_of_eight(n, t, S, L, cascade, crt):
    assert work.cascade_bytes(n, t, 30, rows=8) == cascade
    assert work.crt_bytes(n, t, 30, S, L, 28, rows=8) == crt


@pytest.mark.parametrize("name", ["paper-n4096-t6v30", "hestd128-n8192-t7v30"])
def test_config_bytes_reads_the_config_files(name):
    cfg = json.loads((work.PEAKS_FILE.parent / "configs" / f"{name}.json").read_text())
    got = work.config_bytes(cfg, rows=8)
    assert got["cascade"] == work.cascade_bytes(cfg["n"], cfg["t"], cfg["v"], 8)
    assert got["crt"] > got["cascade"]


def test_peaks_known_kind():
    peaks = work.peaks_for("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in peaks["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5"])
def test_peaks_refuse_unknown_kind(kind):
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks_for(kind)
