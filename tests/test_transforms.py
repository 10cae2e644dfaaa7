"""Property tests for sequence transforms: orthonormality, invertibility,
energy concentration, Theorem 1, optimal bit allocation (paper §3, App. A)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bitalloc, error_bounds as EB, quant as Q
from repro.core import transforms as T
from repro.core.calibration import SiteStats, toeplitz_fraction
from repro.core.stamp import StampConfig, stamp_fake_quant
from repro.data.pipeline import ar_features

jax.config.update("jax_platform_name", "cpu")

KINDS = ["dwt", "dct", "wht"]


def correlated(shape, rho=0.95, seed=0):
    return jnp.asarray(ar_features(shape, rho=rho, seed=seed))


class TestOrthonormal:
    @pytest.mark.parametrize("kind", KINDS)
    @pytest.mark.parametrize("skip_first", [False, True])
    def test_roundtrip(self, kind, skip_first):
        x = correlated((2, 128, 32))
        tx = T.sequence_transform(x, kind, levels=4, skip_first=skip_first)
        back = T.inverse_sequence_transform(tx, kind, levels=4,
                                            skip_first=skip_first)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                                   atol=1e-4)

    @pytest.mark.parametrize("kind", KINDS)
    def test_norm_preserved(self, kind):
        """Eq. 10: orthogonal L leaves the Frobenius norm unchanged."""
        x = correlated((2, 64, 16), seed=1)
        tx = T.sequence_transform(x, kind, levels=3)
        assert abs(float(jnp.linalg.norm(tx) / jnp.linalg.norm(x)) - 1) < 1e-4

    @settings(deadline=None, max_examples=15)
    @given(s=st.sampled_from([32, 48, 64, 100, 128]),
           seed=st.integers(0, 50))
    def test_dwt_roundtrip_odd_lengths(self, s, seed):
        """Non-pow2 lengths: identity-block fallback stays invertible."""
        x = correlated((1, s, 8), seed=seed)
        tx = T.haar_dwt(x, levels=3)
        back = T.haar_idwt(tx, levels=3)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                                   atol=1e-4)

    @pytest.mark.parametrize("kind", ["dwt", "wht"])
    @pytest.mark.parametrize("n", [8, 100, 127, 128])
    @pytest.mark.parametrize("levels", [1, 3, 4])
    @pytest.mark.parametrize("skip_first", [False, True])
    def test_dense_matrix_matches_butterflies(self, kind, n, levels,
                                              skip_first):
        """The (n, n) matrix the fused kernels apply on the MXU is the
        butterfly transform, and its transpose is the inverse."""
        m = T.sequence_matrix(kind, n, levels, skip_first)
        np.testing.assert_allclose(m @ m.T, np.eye(n), atol=1e-6)
        x = correlated((1, n, 16), seed=n)
        tx = T.sequence_transform(x, kind, levels=levels,
                                  skip_first=skip_first)
        np.testing.assert_allclose(np.einsum("st,btd->bsd", m, x),
                                   np.asarray(tx), atol=1e-5)
        assert not m.flags.writeable      # cached: shared by every caller

    def test_dwt2d_roundtrip(self):
        x = correlated((2, 16 * 16, 8), seed=2)
        tx = T.haar_dwt_2d(x, (16, 16), levels=3)
        back = T.haar_idwt_2d(tx, (16, 16), levels=3)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                                   atol=1e-4)

    def test_klt_roundtrip(self):
        x = correlated((4, 32, 16), seed=3)
        stats = SiteStats.empty(32, 16)
        stats.update(np.asarray(x))
        basis = stats.klt()
        tx = T.apply_matrix(x, basis)
        back = T.apply_matrix(tx, basis, inverse=True)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                                   atol=1e-3)


class TestEnergyConcentration:
    def test_ordering_klt_best(self):
        """§3.2: KLT is the optimal energy compactor; DCT ≈ KLT on
        Toeplitz-ish data; DWT concentrates into the first s/2^L band."""
        x = correlated((8, 64, 32), rho=0.95, seed=4)
        stats = SiteStats.empty(64, 32)
        stats.update(np.asarray(x))

        def head_energy(kind):
            e = stats.energy_profile(kind, levels=3)
            es = np.sort(e)[::-1]
            return es[:8].sum() / es.sum()

        klt = head_energy("klt")
        dct = head_energy("dct")
        dwt = head_energy("dwt")
        uniform = 8 / 64
        assert klt >= dct - 1e-3 >= 0
        assert min(klt, dct, dwt) > 1.5 * uniform
        assert klt >= dwt - 1e-3

    def test_toeplitz_premise(self):
        x = correlated((8, 64, 32), rho=0.95, seed=5)
        stats = SiteStats.empty(64, 32)
        stats.update(np.asarray(x))
        assert toeplitz_fraction(stats.autocorr) > 0.9

    def test_dwt_energy_in_lowpass_band(self):
        x = correlated((4, 128, 16), rho=0.95, seed=6)
        tx = T.haar_dwt(x, levels=3)
        e = np.asarray(jnp.sum(tx**2, axis=(0, -1)))
        assert e[:16].sum() / e.sum() > 0.6


class TestTheorem1:
    @settings(deadline=None, max_examples=15)
    @given(seed=st.integers(0, 100), num_hi=st.sampled_from([4, 16, 32]))
    def test_bound_holds(self, seed, num_hi):
        x = correlated((2, 64, 32), seed=seed)
        tx = T.haar_dwt(x, levels=3)
        bits = bitalloc.two_level_bits(64, num_hi)
        q = Q.fake_quant(tx, bits, axis=-1)
        err = float(jnp.sum((q - tx) ** 2))
        bound = float(EB.theorem1_bound(tx, bits))
        assert err <= bound * (1 + 1e-4)

    def test_eq10_orthogonal_invariance(self):
        """L(X; L) == L(LX) for orthogonal L (Appendix A.1)."""
        x = correlated((2, 64, 16), seed=7)
        tx = T.haar_dwt(x, levels=3)
        q = Q.fake_quant(tx, 4, axis=-1)
        err_transformed = float(jnp.sum((q - tx) ** 2))
        back = T.haar_idwt(q, levels=3)
        err_original = float(jnp.sum((back - x) ** 2))
        assert abs(err_transformed - err_original) / err_original < 1e-3


class TestBitAllocation:
    def test_eq18_matches_closed_form(self):
        e = np.array([16.0, 4.0, 1.0, 0.25])
        b = np.asarray(bitalloc.optimal_bits(jnp.asarray(e), 16.0))
        assert abs(b.sum() - 16.0) < 1e-4
        # b_i - b_j == log2 sqrt(e_i / e_j)
        assert abs((b[0] - b[1]) - 1.0) < 1e-5

    def test_eq18_is_optimal_vs_perturbations(self):
        """Perturbing the optimal allocation never lowers the Thm-1 bound."""
        rng = np.random.default_rng(0)
        e = jnp.asarray(rng.uniform(0.1, 10.0, 16).astype(np.float32))
        b_opt = bitalloc.optimal_bits(e, 64.0)
        base = float(bitalloc.bound_value(e, b_opt, d=32))
        for _ in range(20):
            delta = rng.normal(size=16).astype(np.float32) * 0.3
            delta -= delta.mean()   # keep the budget fixed
            perturbed = float(bitalloc.bound_value(e, b_opt + delta, d=32))
            assert perturbed >= base - 1e-4

    def test_jensen_gap(self):
        """Appendix A.3: concentrated ≤ uniform."""
        rng = np.random.default_rng(1)
        e = jnp.asarray(rng.lognormal(0, 2.0, 64).astype(np.float32))
        uniform, conc = EB.uniform_vs_concentrated(e, avg_bits=4.0, d=32)
        assert float(conc) <= float(uniform) + 1e-6

    def test_integer_allocation_respects_budget(self):
        rng = np.random.default_rng(2)
        e = rng.lognormal(0, 1.5, 32)
        b = bitalloc.integer_rounded_allocation(e, total_bits=128)
        assert b.sum() == 128
        assert b.min() >= 2 and b.max() <= 8


class TestStampEndToEnd:
    def test_stamp_beats_uniform_at_matched_bits(self):
        """The paper's headline: DWT + mixed precision < uniform error."""
        x = correlated((4, 512, 64), rho=0.95, seed=8)
        cfg = StampConfig(num_hi_tokens=32, skip_first_token=False)
        avg = cfg.average_bits(512)
        uniform = Q.fake_quant(x, avg, axis=-1)
        stamped = stamp_fake_quant(x, cfg)
        err_u = float(jnp.sum((uniform - x) ** 2))
        err_s = float(jnp.sum((stamped - x) ** 2))
        assert err_s < err_u

    @pytest.mark.parametrize("kind", KINDS)
    def test_all_transforms_improve(self, kind):
        """Fig. 7: DCT ≈ WHT ≈ DWT all beat no-transform."""
        x = correlated((4, 256, 32), rho=0.95, seed=9)
        cfg = StampConfig(seq_transform=kind, num_hi_tokens=32,
                          skip_first_token=False)
        none_cfg = StampConfig(seq_transform="none", num_hi_tokens=32,
                               skip_first_token=False)
        err_t = float(jnp.sum((stamp_fake_quant(x, cfg) - x) ** 2))
        err_n = float(jnp.sum((stamp_fake_quant(x, none_cfg) - x) ** 2))
        assert err_t < err_n

    def test_skip_first_token_preserves_it(self):
        x = correlated((1, 64, 16), seed=10)
        x = x.at[0, 0].set(100.0)   # attention-sink outlier
        cfg = StampConfig(num_hi_tokens=8, skip_first_token=True)
        tx = jnp.asarray(
            stamp_fake_quant(x, cfg))
        # first token still carries its outlier (hi-precision, unmixed)
        assert float(jnp.abs(tx[0, 0] - x[0, 0]).max()) < \
            float(jnp.abs(x[0, 0]).max()) * 0.02
