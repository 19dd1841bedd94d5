"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp ref oracles,
swept over shapes and configurations."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import params as params_mod
from repro.core import polymul as pm
from repro.kernels import ops
from repro.kernels import ntt as ntt_kernels


@pytest.fixture(scope="module", params=[(3, 30, 64), (3, 30, 256), (6, 30, 128)])
def p(request):
    t, v, n = request.param
    return params_mod.make_params(n=n, t=t, v=v)


def _rand_res(p, rows, seed):
    rng = np.random.default_rng(seed)
    chans = [
        rng.integers(0, int(q), size=(rows, p.n)) for q in p.plan.qs
    ]
    return jnp.asarray(np.stack(chans))


class TestNttKernels:
    @pytest.mark.parametrize("rows", [1, 3, 8, 17])
    def test_forward_matches_ref(self, p, rows):
        a = _rand_res(p, rows, rows)
        got = ops.ntt_forward(a, p, use_pallas=True)
        want = ops.ntt_forward(a, p, use_pallas=False)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("rows", [1, 8])
    def test_inverse_matches_ref(self, p, rows):
        a = _rand_res(p, rows, 10 + rows)
        got = ops.ntt_inverse(a, p, use_pallas=True)
        want = ops.ntt_inverse(a, p, use_pallas=False)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.slow  # interpret-mode Pallas sweep over presets x rows
    @pytest.mark.parametrize("rows", [1, 5, 8])
    def test_fused_matches_ref_and_schoolbook(self, p, rows):
        a = _rand_res(p, rows, 20 + rows)
        b = _rand_res(p, rows, 30 + rows)
        got = ops.negacyclic_mul(a, b, p, use_pallas=True)
        want = ops.negacyclic_mul(a, b, p, use_pallas=False)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        # spot-check channel 0 row 0 against schoolbook
        q0 = int(p.plan.qs[0])
        sb = pm.schoolbook_negacyclic(
            np.asarray(a)[0, 0].tolist(), np.asarray(b)[0, 0].tolist(), q0
        )
        assert np.asarray(got)[0, 0].tolist() == sb

    def test_roundtrip_via_kernels(self, p):
        a = _rand_res(p, 4, 99)
        fa = ops.ntt_forward(a, p, use_pallas=True)
        back = ops.ntt_inverse(fa, p, use_pallas=True)
        assert np.array_equal(np.asarray(back), np.asarray(a))

    @pytest.mark.parametrize("row_blk", [2, 4, 8])
    def test_row_block_sweep(self, row_blk):
        p = params_mod.make_params(n=64, t=3, v=30)
        a = _rand_res(p, 8, row_blk)
        ct = p.tables
        got = ntt_kernels.ntt_channels_pallas(
            a, jnp.asarray(ct.qs), jnp.asarray(ct.fwd), row_blk=row_blk,
            interpret=True,
        )
        want = ops.ntt_forward(a, p, use_pallas=False)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_dtype_int32_small_modulus(self):
        """int32 lane variant: works when q < 2^15 (products < 2^31)."""
        from repro.core import ntt as ntt_core

        q, n = 12289, 64  # 2n | q-1
        tb = ntt_core.make_tables(q, n)
        rng = np.random.default_rng(0)
        a = rng.integers(0, q, size=(1, 2, n)).astype(np.int32)
        got = ntt_kernels.ntt_channels_pallas(
            jnp.asarray(a),
            jnp.asarray([q], dtype=jnp.int32),
            jnp.asarray(tb.fwd[None, :].astype(np.int32)),
            interpret=True,
        )
        want = ntt_core.ntt_raw(jnp.asarray(a[0]).astype(jnp.int64), jnp.asarray(tb.fwd), q)
        assert np.array_equal(np.asarray(got)[0], np.asarray(want).astype(np.int32))


class TestCrtKernels:
    def test_decompose_matches_ref(self, p):
        rng = np.random.default_rng(1)
        z = jnp.asarray(rng.integers(0, 1 << p.plan.v, size=(300, p.plan.seg_count)))
        got = ops.rns_decompose(z, p, use_pallas=True)
        want = ops.rns_decompose(z, p, use_pallas=False)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_compose_matches_ref(self, p):
        rng = np.random.default_rng(2)
        res = jnp.asarray(
            np.stack([rng.integers(0, int(q), size=513) for q in p.plan.qs])
        )
        got = ops.rns_compose(res, p, use_pallas=True)
        want = ops.rns_compose(res, p, use_pallas=False)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_end_to_end_kernel_pipeline(self):
        """segments -> decompose -> fused mul -> compose, all Pallas,
        vs the bigint schoolbook."""
        import random

        p = params_mod.make_params(n=64, t=3, v=30)
        rng = random.Random(5)
        a = [rng.randrange(p.q) for _ in range(p.n)]
        b = [rng.randrange(p.q) for _ in range(p.n)]
        za = jnp.asarray(pm.ints_to_segments(a, p.plan))
        zb = jnp.asarray(pm.ints_to_segments(b, p.plan))
        ra = ops.rns_decompose(za, p)[:, None, :]  # (t, 1, n)
        rb = ops.rns_decompose(zb, p)[:, None, :]
        rp = ops.negacyclic_mul(ra, rb, p)[:, 0, :]
        limbs = ops.rns_compose(rp, p)
        got = pm.limbs_out_to_ints(np.asarray(limbs), p.plan)
        assert got == pm.schoolbook_negacyclic(a, b, p.q)
