"""Bytes and operations of the served programs, from their shapes."""

import pytest

from bench import roofline, spec

V5E = spec.peaks("TPU v5 lite")


def test_flat_scan_counts_corpus_and_queries_once():
    w = roofline.flat_scan(rows=1_000_000, dim=768, queries=32)
    assert w["bytes"] == (1_000_000 + 32) * 768 * 4
    assert w["flops"] == 2 * 1_000_000 * 768 * 32


def test_beam_gather_counts_rows_at_their_own_width():
    # batch 32 x expansion width 4 x M0 32 rows of 768 float32
    w = roofline.beam_gather(32 * 4 * 32, 768)
    assert w["bytes"] == 32 * 4 * 32 * 768 * 4


def test_share_takes_the_binding_roof():
    w = roofline.flat_scan(1_000_000, 768, 32)      # bytes bind at batch 32
    least = w["bytes"] / V5E["hbm_bytes_per_s"]
    assert roofline.share(w, least, V5E) == pytest.approx(100.0)
    assert roofline.share(w, 2 * least, V5E) == pytest.approx(50.0)
    big = roofline.flat_scan(1_000_000, 768, 4096)  # past the ridge: flops
    least = big["flops"] / V5E["bf16_flops_per_s"]
    assert roofline.share(big, least, V5E) == pytest.approx(100.0)
    assert roofline.share(w, 0.0, V5E) is None
