"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode.

Every kernel is executed with interpret=True (kernel body evaluated on CPU)
and asserted allclose against ref.py — the correctness contract required for
each kernel (assignment deliverable c).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # deterministic shim keeps properties runnable
    from _hypothesis_fallback import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.beam_gather import (beam_gather_adc_kernel,
                                       beam_gather_hamming_kernel,
                                       beam_gather_kernel, gather_table)
from repro.kernels.hamming import hamming_kernel
from repro.kernels.l2 import l2_distance_kernel
from repro.kernels.pq_adc import pq_adc_kernel

RNG = np.random.RandomState(0)


class TestL2Kernel:
    @pytest.mark.parametrize("q,n,d", [
        (8, 128, 64),         # tile-aligned
        (7, 300, 130),        # padding on every axis
        (64, 1024, 784),      # fashion-mnist dims
        (1, 33, 128),         # single query, sift dims
        (3, 50, 16),          # tiny
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, q, n, d, dtype):
        qs = jnp.asarray(RNG.randn(q, d), dtype)
        xs = jnp.asarray(RNG.randn(n, d), dtype)
        got = l2_distance_kernel(qs, xs, tq=16, tn=128, tk=64, interpret=True)
        want = ref.l2_distance_ref(qs, xs)
        tol = 2e-4 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("q,n,d", [(9, 200, 96), (16, 128, 128)])
    def test_dot_mode(self, q, n, d):
        qs = jnp.asarray(RNG.randn(q, d), jnp.float32)
        xs = jnp.asarray(RNG.randn(n, d), jnp.float32)
        got = l2_distance_kernel(qs, xs, mode="dot", tq=8, tn=64, tk=32,
                                 interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.dot_distance_ref(qs, xs)),
                                   rtol=2e-4, atol=2e-4)

    def test_tile_shape_independence(self):
        """Result must not depend on the BlockSpec tiling chosen."""
        qs = jnp.asarray(RNG.randn(13, 70), jnp.float32)
        xs = jnp.asarray(RNG.randn(111, 70), jnp.float32)
        a = l2_distance_kernel(qs, xs, tq=4, tn=32, tk=16, interpret=True)
        b = l2_distance_kernel(qs, xs, tq=16, tn=256, tk=70, interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


class TestPQADCKernel:
    @pytest.mark.parametrize("q,n,m,k", [
        (5, 700, 8, 64),
        (2, 100, 16, 256),    # uint8 full range
        (9, 333, 4, 16),      # fast-scan-like small k
        (1, 64, 32, 256),
    ])
    def test_matches_ref(self, q, n, m, k):
        lut = jnp.asarray(RNG.rand(q, m, k), jnp.float32)
        codes = jnp.asarray(RNG.randint(0, k, (n, m)), jnp.uint8)
        got = pq_adc_kernel(lut, codes, tq=4, tn=256, interpret=True)
        want = ref.pq_adc_ref(lut, codes)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_uint16_codes(self):
        lut = jnp.asarray(RNG.rand(2, 4, 512), jnp.float32)
        codes = jnp.asarray(RNG.randint(0, 512, (50, 4)), jnp.uint16)
        got = pq_adc_kernel(lut, codes, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.pq_adc_ref(lut, codes)),
                                   rtol=1e-5, atol=1e-5)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 6), st.integers(10, 80), st.integers(1, 3),
           st.integers(0, 1000))
    def test_property_sweep(self, q, n, m_exp, seed):
        m = 2 ** m_exp
        rng = np.random.RandomState(seed)
        lut = jnp.asarray(rng.rand(q, m, 16), jnp.float32)
        codes = jnp.asarray(rng.randint(0, 16, (n, m)), jnp.uint8)
        got = pq_adc_kernel(lut, codes, tq=2, tn=128, m_chunk=2,
                            interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.pq_adc_ref(lut, codes)),
                                   rtol=1e-5, atol=1e-5)


class TestHammingKernel:
    @pytest.mark.parametrize("q,n,w", [
        (5, 700, 8), (33, 129, 4), (2, 50, 16), (1, 1, 1),
    ])
    def test_matches_ref(self, q, n, w):
        qc = jnp.asarray(RNG.randint(0, 2 ** 31, (q, w)), jnp.uint32)
        xc = jnp.asarray(RNG.randint(0, 2 ** 31, (n, w)), jnp.uint32)
        got = hamming_kernel(qc, xc, tq=16, tn=128, interpret=True)
        want = ref.hamming_ref(qc, xc)
        assert (np.asarray(got) == np.asarray(want)).all()

    def test_all_ones_and_zeros(self):
        z = jnp.zeros((3, 4), jnp.uint32)
        o = jnp.full((5, 4), 0xFFFFFFFF, jnp.uint32)
        got = np.asarray(hamming_kernel(z, o, interpret=True))
        assert (got == 128).all()


class TestBeamGatherKernel:
    """Fused gather-distance kernels (wide-beam traversal) vs refs."""

    @pytest.mark.parametrize("n,d,l,tb", [
        (256, 64, 128, 32),    # tile-aligned
        (100, 48, 37, 16),     # padding on the id axis
        (50, 16, 1, 8),        # single id (the entry-point init call)
        (33, 130, 65, 64),
    ])
    @pytest.mark.parametrize("mode", ["l2", "dot"])
    def test_matches_ref(self, n, d, l, tb, mode):
        corpus = jnp.asarray(RNG.randn(n, d), jnp.float32)
        q = jnp.asarray(RNG.randn(d), jnp.float32)
        ids = jnp.asarray(RNG.randint(0, n, l), jnp.int32)
        got = beam_gather_kernel(q, ids, gather_table(corpus), mode=mode,
                                 tb=tb, interpret=True)
        want = (ref.beam_gather_l2_ref(q, ids, corpus) if mode == "l2"
                else ref.beam_gather_dot_ref(q, ids, corpus))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_duplicate_and_boundary_ids(self):
        """Gathers are arbitrary: repeated rows and rows 0 / N-1 must work."""
        corpus = jnp.asarray(RNG.randn(40, 24), jnp.float32)
        q = jnp.asarray(RNG.randn(24), jnp.float32)
        ids = jnp.asarray([0, 39, 7, 7, 7, 0, 39, 13], jnp.int32)
        got = beam_gather_kernel(q, ids, gather_table(corpus), tb=4,
                                 interpret=True)
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(ref.beam_gather_l2_ref(q, ids, corpus)),
            rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("n,m,k,l", [
        (200, 8, 64, 48), (77, 4, 16, 13), (64, 16, 256, 128),
    ])
    def test_adc_matches_ref(self, n, m, k, l):
        lut = jnp.asarray(RNG.rand(m, k), jnp.float32)
        codes = jnp.asarray(RNG.randint(0, k, (n, m)), jnp.uint8)
        ids = jnp.asarray(RNG.randint(0, n, l), jnp.int32)
        got = beam_gather_adc_kernel(lut, ids, gather_table(codes), tb=16,
                                     m_chunk=4, interpret=True)
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(ref.beam_gather_adc_ref(lut, ids, codes)),
            rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("n,w,l", [(150, 8, 40), (64, 4, 7), (20, 1, 20)])
    def test_hamming_matches_ref(self, n, w, l):
        qc = jnp.asarray(RNG.randint(0, 2 ** 31, w), jnp.uint32)
        xc = jnp.asarray(RNG.randint(0, 2 ** 31, (n, w)), jnp.uint32)
        ids = jnp.asarray(RNG.randint(0, n, l), jnp.int32)
        got = beam_gather_hamming_kernel(qc, ids, gather_table(xc), tb=16,
                                         interpret=True)
        want = ref.beam_gather_hamming_ref(qc, ids, xc)
        assert (np.asarray(got) == np.asarray(want)).all()

    def test_ops_dispatch_parity(self):
        """force_ref=True and the interpret-mode kernel agree through the
        public dispatchers."""
        corpus = jnp.asarray(RNG.randn(60, 32), jnp.float32)
        q = jnp.asarray(RNG.randn(32), jnp.float32)
        ids = jnp.asarray(RNG.randint(0, 60, 21), jnp.int32)
        table = gather_table(corpus)
        for mode in ("l2", "dot"):
            a = ops.beam_gather_distances(q, ids, table, mode=mode,
                                          force_ref=True)
            b = ops.beam_gather_distances(q, ids, table, mode=mode,
                                          force_ref=False, tb=8)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("force_ref", [True, False])
    def test_plain_matrix_refused(self, force_ref):
        """Kernels and oracles take gather tables only: a plain corpus
        would be padded and copied on every call of the search loop."""
        corpus = jnp.asarray(RNG.randn(60, 32), jnp.float32)
        ids = jnp.asarray(RNG.randint(0, 60, 21), jnp.int32)
        with pytest.raises(ValueError, match="gather table"):
            ops.beam_gather_distances(corpus[0], ids, corpus,
                                      force_ref=force_ref)
        codes = gather_table(jnp.asarray(RNG.randint(0, 16, (60, 8)),
                                         jnp.int32))
        with pytest.raises(ValueError, match="gather table"):
            ops.beam_gather_hamming(jnp.zeros((8,), jnp.uint32), ids, codes,
                                    force_ref=force_ref)


class TestPairGatherKernel:
    """Fused candidate-pair distance kernel (bulk-build Alg-4 prune)."""

    @pytest.mark.parametrize("n,d,c", [
        (128, 32, 64),      # aligned
        (100, 48, 37),      # id-axis padding (37 -> 40 lanes)
        (50, 16, 1),        # single candidate
        (33, 130, 19),
    ])
    @pytest.mark.parametrize("mode", ["l2", "dot"])
    def test_matches_ref(self, n, d, c, mode):
        from repro.kernels.bulk_prune import pair_gather_kernel
        corpus = jnp.asarray(RNG.randn(n, d), jnp.float32)
        ids = jnp.asarray(RNG.randint(0, n, c), jnp.int32)
        got = pair_gather_kernel(ids, gather_table(corpus), mode=mode,
                                 interpret=True)
        want = (ref.pair_gather_l2_ref(ids, corpus) if mode == "l2"
                else ref.pair_gather_dot_ref(ids, corpus))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_duplicate_ids_give_zero_l2(self):
        from repro.kernels.bulk_prune import pair_gather_kernel
        corpus = jnp.asarray(RNG.randn(30, 24), jnp.float32)
        ids = jnp.asarray([5, 5, 0, 29, 5], jnp.int32)
        got = np.asarray(pair_gather_kernel(ids, gather_table(corpus),
                                            interpret=True))
        assert got.shape == (5, 5)
        np.testing.assert_allclose(np.diag(got), 0.0, atol=1e-4)
        np.testing.assert_allclose(got[0, 1], 0.0, atol=1e-4)  # dup pair

    def test_ops_dispatch_parity(self):
        corpus = jnp.asarray(RNG.randn(60, 32), jnp.float32)
        ids = jnp.asarray(RNG.randint(0, 60, 21), jnp.int32)
        table = gather_table(corpus)
        for mode in ("l2", "dot"):
            a = ops.pair_gather_distances(ids, table, mode=mode,
                                          force_ref=True)
            b = ops.pair_gather_distances(ids, table, mode=mode,
                                          force_ref=False)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


class TestSLSTMKernel:
    """Fused weight-resident sLSTM kernel vs the scan oracle (§Perf 4.4)."""

    @pytest.mark.parametrize("b,s,d,h,chunk", [
        (2, 64, 32, 4, 16),
        (1, 32, 16, 2, 32),     # single chunk
        (3, 96, 64, 8, 24),
    ])
    def test_matches_ref(self, b, s, d, h, chunk):
        from repro.kernels.slstm import slstm_sequence_kernel
        rng = np.random.RandomState(b + s)
        blk = d // h
        gates = jnp.asarray(rng.randn(b, s, 4 * d), jnp.float32)
        r = jnp.asarray(0.3 * rng.randn(4, h, blk, blk), jnp.float32)
        bias = jnp.asarray(rng.randn(4 * d), jnp.float32)
        got = slstm_sequence_kernel(gates, r, bias, n_heads=h, chunk=chunk,
                                    interpret=True)
        want = ref.slstm_sequence_ref(gates, r, bias, n_heads=h)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)

    def test_chunk_invariance(self):
        from repro.kernels.slstm import slstm_sequence_kernel
        rng = np.random.RandomState(7)
        gates = jnp.asarray(rng.randn(2, 48, 64), jnp.float32)
        r = jnp.asarray(0.3 * rng.randn(4, 4, 4, 4), jnp.float32)
        bias = jnp.asarray(rng.randn(64), jnp.float32)
        a = slstm_sequence_kernel(gates, r, bias, n_heads=4, chunk=12,
                                  interpret=True)
        b = slstm_sequence_kernel(gates, r, bias, n_heads=4, chunk=48,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)

    def test_matches_model_cell(self):
        """The kernel's semantics == the model's recurrent cell."""
        from repro.kernels.slstm import slstm_sequence_kernel
        from repro.models.config import ModelConfig
        from repro.models.recurrent import (_slstm_cell, init_slstm,
                                            slstm_init_state)
        import jax
        cfg = ModelConfig(name="t", family="ssm", n_layers=2, d_model=32,
                          n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=16,
                          block_pattern=("slstm", "slstm"))
        p = init_slstm(jax.random.PRNGKey(0), cfg)
        gates = jnp.asarray(RNG.randn(2, 24, 128), jnp.float32)
        state = slstm_init_state(cfg, 2)
        hs = []
        for t in range(24):
            h, state = _slstm_cell(p, gates[:, t], state, 4)
            hs.append(h)
        want = jnp.stack(hs, axis=1)
        got = slstm_sequence_kernel(gates, p["r"], p["b"], n_heads=4,
                                    chunk=8, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)

    def test_ops_dispatcher_force_ref_parity(self):
        """`ops.slstm_sequence` routes ref vs kernel per the registry
        contract (the qlint PAL004 rule requires this dispatcher)."""
        rng = np.random.RandomState(11)
        gates = jnp.asarray(rng.randn(2, 24, 64), jnp.float32)
        r = jnp.asarray(0.3 * rng.randn(4, 4, 4, 4), jnp.float32)
        bias = jnp.asarray(rng.randn(64), jnp.float32)
        a = ops.slstm_sequence(gates, r, bias, n_heads=4, force_ref=True)
        b = ops.slstm_sequence(gates, r, bias, n_heads=4, chunk=8,
                               force_ref=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=3e-5)
        from repro.kernels import slstm_sequence as exported
        assert exported is ops.slstm_sequence


class TestOpsDispatch:
    def test_force_ref_matches_kernel(self):
        qs = jnp.asarray(RNG.randn(4, 32), jnp.float32)
        xs = jnp.asarray(RNG.randn(40, 32), jnp.float32)
        a = ops.l2_distances(qs, xs, force_ref=True)
        b = ops.l2_distances(qs, xs, force_ref=False, tq=4, tn=32, tk=32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)

    def test_all_ops_callable(self):
        qs = jnp.asarray(RNG.randn(2, 16), jnp.float32)
        xs = jnp.asarray(RNG.randn(8, 16), jnp.float32)
        assert ops.dot_distances(qs, xs).shape == (2, 8)
        lut = jnp.asarray(RNG.rand(2, 4, 8), jnp.float32)
        codes = jnp.asarray(RNG.randint(0, 8, (9, 4)), jnp.uint8)
        assert ops.pq_adc_distances(lut, codes).shape == (2, 9)
        qc = jnp.asarray(RNG.randint(0, 2 ** 31, (2, 2)), jnp.uint32)
        xc = jnp.asarray(RNG.randint(0, 2 ** 31, (5, 2)), jnp.uint32)
        assert ops.hamming_distances(qc, xc).shape == (2, 5)
