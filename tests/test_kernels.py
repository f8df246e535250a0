"""Pallas kernel correctness: shape/dtype sweeps, interpret=True vs the
pure-jnp oracles in kernels/ref.py, plus an end-to-end SSD equivalence check
against a naive recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_decode_attention import (paged_decode_attention,
                                                  paged_mla_decode_attention)
from repro.kernels.ssd_scan import ssd_intra, ssd_slab_decode


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,nq,nkv,s,h", [
        (1, 4, 4, 128, 64),    # MHA
        (2, 4, 2, 128, 64),    # GQA
        (1, 4, 1, 256, 128),   # MQA, two kv blocks per q row
        (1, 2, 2, 512, 64),    # multiple q and kv blocks
    ])
    def test_causal_matches_ref(self, b, nq, nkv, s, h, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = _rand(ks[0], (b, nq, s, h), dtype)
        k = _rand(ks[1], (b, nkv, s, h), dtype)
        v = _rand(ks[2], (b, nkv, s, h), dtype)
        got = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                              interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **TOL[dtype])

    def test_non_causal(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = _rand(ks[0], (1, 2, 256, 64), jnp.float32)
        k = _rand(ks[1], (1, 2, 256, 64), jnp.float32)
        v = _rand(ks[2], (1, 2, 256, 64), jnp.float32)
        got = flash_attention(q, k, v, causal=False, block_q=128, block_k=128,
                              interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_scale_override(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = _rand(ks[0], (1, 1, 128, 64), jnp.float32)
        k = _rand(ks[1], (1, 1, 128, 64), jnp.float32)
        v = _rand(ks[2], (1, 1, 128, 64), jnp.float32)
        got = flash_attention(q, k, v, causal=True, scale=0.5, block_q=64,
                              block_k=64, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, scale=0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,nq,nkv,smax,h", [
        (2, 4, 4, 256, 64),
        (2, 8, 2, 512, 64),
        (1, 4, 1, 1024, 128),
    ])
    def test_matches_ref(self, b, nq, nkv, smax, h, dtype):
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = _rand(ks[0], (b, nq, h), dtype)
        k = _rand(ks[1], (b, nkv, smax, h), dtype)
        v = _rand(ks[2], (b, nkv, smax, h), dtype)
        lengths = jax.random.randint(ks[3], (b,), 1, smax + 1)
        got = decode_attention(q, k, v, lengths, block_k=128, interpret=True)
        want = ref.decode_attention_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **TOL[dtype])

    def test_ragged_lengths_skip_blocks(self):
        """Tiny lengths: only the masked prefix participates."""
        b, nq, smax, h = 3, 2, 512, 64
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q = _rand(ks[0], (b, nq, h), jnp.float32)
        k = _rand(ks[1], (b, nq, smax, h), jnp.float32)
        v = _rand(ks[2], (b, nq, smax, h), jnp.float32)
        lengths = jnp.array([1, 7, 130])
        got = decode_attention(q, k, v, lengths, block_k=128, interpret=True)
        want = ref.decode_attention_ref(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestPagedDecodeAttention:
    """Block-table KV gather: the paged kernel must equal dense decode
    attention over the gathered contiguous view (kernels/ref.py oracle)."""

    @staticmethod
    def _make(key, b, nq, nkv, h, nb, bs, w, dtype):
        ks = jax.random.split(key, 4)
        q = _rand(ks[0], (b, nq, h), dtype)
        k_pool = _rand(ks[1], (nb, bs, nkv, h), dtype)
        v_pool = _rand(ks[2], (nb, bs, nkv, h), dtype)
        # each row gets w distinct pool blocks, deliberately out of order
        perm = jax.random.permutation(ks[3], nb)[: b * w]
        tables = perm.reshape(b, w).astype(jnp.int32)
        return q, k_pool, v_pool, tables

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,nq,nkv,h,nb,bs,w", [
        (2, 4, 4, 64, 16, 16, 4),    # MHA
        (2, 8, 2, 64, 32, 32, 6),    # GQA
        (1, 4, 1, 128, 8, 64, 3),    # MQA
    ])
    def test_matches_ref(self, b, nq, nkv, h, nb, bs, w, dtype):
        key = jax.random.PRNGKey(11)
        q, kp, vp, tables = self._make(key, b, nq, nkv, h, nb, bs, w, dtype)
        lengths = jax.random.randint(jax.random.fold_in(key, 1), (b,), 1,
                                     w * bs + 1)
        got = paged_decode_attention(q, kp, vp, tables, lengths,
                                     interpret=True)
        want = ref.paged_decode_attention_ref(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **TOL[dtype])

    def test_short_lengths_skip_blocks(self):
        """Rows whose length covers only the first block(s): remaining table
        entries may point anywhere (pad blocks) without affecting output."""
        b, nq, nkv, h, nb, bs, w = 3, 2, 2, 64, 12, 16, 4
        q, kp, vp, tables = self._make(jax.random.PRNGKey(12), b, nq, nkv, h,
                                       nb, bs, w, jnp.float32)
        lengths = jnp.array([1, 16, 17], jnp.int32)
        got = paged_decode_attention(q, kp, vp, tables, lengths,
                                     interpret=True)
        want = ref.paged_decode_attention_ref(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        # scribbling on the dead tail blocks must not change the output
        tables2 = tables.at[0, 1:].set(0).at[1, 1:].set(0)
        got2 = paged_decode_attention(q, kp, vp, tables2, lengths,
                                      interpret=True)
        np.testing.assert_array_equal(np.asarray(got[:2]),
                                      np.asarray(got2[:2]))

    def test_matches_masked_dense_kernel(self):
        """Paged and masked-dense kernels share the online-softmax core: on
        the same logical cache they must agree to fp tolerance."""
        b, nq, nkv, h, bs, w = 2, 4, 2, 64, 32, 4
        smax = bs * w
        ks = jax.random.split(jax.random.PRNGKey(13), 3)
        q = _rand(ks[0], (b, nq, h), jnp.float32)
        k = _rand(ks[1], (b, nkv, smax, h), jnp.float32)
        v = _rand(ks[2], (b, nkv, smax, h), jnp.float32)
        lengths = jnp.array([smax, 37], jnp.int32)
        # identity paging: row b uses blocks [b*w, b*w+1, ...)
        tables = (jnp.arange(b)[:, None] * w + jnp.arange(w)[None, :]
                  ).astype(jnp.int32)
        kp = jnp.swapaxes(k, 1, 2).reshape(b * w, bs, nkv, h)
        vp = jnp.swapaxes(v, 1, 2).reshape(b * w, bs, nkv, h)
        dense = decode_attention(q, k, v, lengths, block_k=bs, interpret=True)
        paged = paged_decode_attention(q, kp, vp, tables, lengths,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                                   rtol=1e-6, atol=1e-6)


class TestPagedMLADecodeAttention:
    """Latent block pools: the absorbed-MLA paged kernel must equal the
    gathered-view oracle (key = latent‖rope, value = latent)."""

    @staticmethod
    def _make(key, b, nq, r, pr, nb, bs, w, dtype):
        ks = jax.random.split(key, 5)
        q_lat = _rand(ks[0], (b, nq, r), dtype)
        q_rope = _rand(ks[1], (b, nq, pr), dtype)
        ckv = _rand(ks[2], (nb, bs, r), dtype)
        krope = _rand(ks[3], (nb, bs, pr), dtype)
        perm = jax.random.permutation(ks[4], nb)[: b * w]
        tables = perm.reshape(b, w).astype(jnp.int32)
        return q_lat, q_rope, ckv, krope, tables

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,nq,r,pr,nb,bs,w", [
        (2, 4, 32, 8, 16, 16, 4),
        (1, 8, 64, 16, 12, 32, 3),
    ])
    def test_matches_ref(self, b, nq, r, pr, nb, bs, w, dtype):
        key = jax.random.PRNGKey(21)
        ql, qr, ckv, krope, tables = self._make(key, b, nq, r, pr, nb, bs, w,
                                                dtype)
        lengths = jax.random.randint(jax.random.fold_in(key, 1), (b,), 1,
                                     w * bs + 1)
        scale = (r + pr) ** -0.5
        got = paged_mla_decode_attention(ql, qr, ckv, krope, tables, lengths,
                                         scale=scale, interpret=True)
        want = ref.paged_mla_decode_attention_ref(ql, qr, ckv, krope, tables,
                                                  lengths, scale=scale)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **TOL[dtype])

    def test_short_lengths_skip_blocks(self):
        b, nq, r, pr, nb, bs, w = 3, 2, 32, 8, 12, 16, 4
        ql, qr, ckv, krope, tables = self._make(jax.random.PRNGKey(22), b, nq,
                                                r, pr, nb, bs, w, jnp.float32)
        lengths = jnp.array([1, 16, 17], jnp.int32)
        scale = (r + pr) ** -0.5
        got = paged_mla_decode_attention(ql, qr, ckv, krope, tables, lengths,
                                         scale=scale, interpret=True)
        want = ref.paged_mla_decode_attention_ref(ql, qr, ckv, krope, tables,
                                                  lengths, scale=scale)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        tables2 = tables.at[0, 1:].set(0).at[1, 1:].set(0)
        got2 = paged_mla_decode_attention(ql, qr, ckv, krope, tables2, lengths,
                                          scale=scale, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[:2]), np.asarray(got2[:2]))

    def test_custom_scale(self):
        b, nq, r, pr, nb, bs, w = 1, 2, 16, 8, 8, 16, 2
        ql, qr, ckv, krope, tables = self._make(jax.random.PRNGKey(23), b, nq,
                                                r, pr, nb, bs, w, jnp.float32)
        lengths = jnp.array([20], jnp.int32)
        # MLA scales by the QK head dim (nope+rope), NOT the latent rank
        got = paged_mla_decode_attention(ql, qr, ckv, krope, tables, lengths,
                                         scale=24 ** -0.5, interpret=True)
        want = ref.paged_mla_decode_attention_ref(ql, qr, ckv, krope, tables,
                                                  lengths, scale=24 ** -0.5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestSSDSlabDecode:
    """Slab-pool state gather: one recurrent step addressed through slab
    ids must equal ssd_decode_step on the gathered states."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,h,p,n,g,ns", [
        (2, 4, 16, 24, 1, 8),
        (3, 4, 32, 16, 2, 6),
    ])
    def test_matches_ref(self, b, h, p, n, g, ns, dtype):
        ks = jax.random.split(jax.random.PRNGKey(31), 6)
        pool = _rand(ks[0], (ns, h, p, n), jnp.float32)
        slabs = jax.random.permutation(ks[1], ns)[:b].astype(jnp.int32)
        x = _rand(ks[2], (b, h, p), dtype)
        dt = jax.nn.softplus(_rand(ks[3], (b, h), jnp.float32))
        A = -jnp.abs(_rand(ks[4], (h,), jnp.float32)) * 0.5
        B = _rand(ks[5], (b, g, n), dtype)
        C = _rand(jax.random.fold_in(ks[5], 1), (b, g, n), dtype)
        got_y, got_s = ssd_slab_decode(pool, slabs, x, dt, A, B, C,
                                       interpret=True)
        want_y, want_s = ref.ssd_slab_decode_ref(pool, slabs, x, dt, A, B, C)
        np.testing.assert_allclose(np.asarray(got_y, np.float32),
                                   np.asarray(want_y, np.float32), **TOL[dtype])
        np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                                   rtol=1e-5, atol=1e-5)

    def test_scatter_roundtrip_matches_model_step(self):
        """pool.at[slabs].set(states) after the kernel equals running
        models.ssm.ssd_decode_step on the gathered slabs directly."""
        from repro.models.ssm import ssd_decode_step

        b, h, p, n, ns = 2, 2, 8, 12, 5
        ks = jax.random.split(jax.random.PRNGKey(32), 6)
        pool = _rand(ks[0], (ns, h, p, n), jnp.float32)
        slabs = jnp.array([3, 1], jnp.int32)
        x = _rand(ks[2], (b, h, p), jnp.float32)
        dt = jax.nn.softplus(_rand(ks[3], (b, h), jnp.float32))
        A = -jnp.abs(_rand(ks[4], (h,), jnp.float32)) * 0.5
        B = _rand(ks[5], (b, 1, n), jnp.float32)
        C = _rand(jax.random.fold_in(ks[5], 2), (b, 1, n), jnp.float32)
        y, states = ssd_slab_decode(pool, slabs, x, dt, A, B, C,
                                    interpret=True)
        new_pool = pool.at[slabs].set(states)
        y2, s2 = ssd_decode_step(pool[slabs], x, dt, A, B, C)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y2),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(new_pool[slabs]),
                                   np.asarray(s2), rtol=1e-5, atol=1e-5)
        # untouched slabs stay bit-identical
        rest = np.setdiff1d(np.arange(ns), np.asarray(slabs))
        np.testing.assert_array_equal(np.asarray(new_pool[rest]),
                                      np.asarray(pool[rest]))


class TestSSDIntra:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("m,h,q,p,n", [
        (2, 2, 64, 32, 32),
        (1, 4, 128, 64, 128),
        (3, 1, 256, 64, 64),
    ])
    def test_matches_ref(self, m, h, q, p, n, dtype):
        ks = jax.random.split(jax.random.PRNGKey(5), 5)
        x = _rand(ks[0], (m, h, q, p), dtype)
        dt = jax.nn.softplus(_rand(ks[1], (m, h, q), jnp.float32))
        dA = -jnp.abs(_rand(ks[2], (m, h, q), jnp.float32)) * 0.1
        B = _rand(ks[3], (m, q, n), dtype)
        C = _rand(ks[4], (m, q, n), dtype)
        got_y, got_s = ssd_intra(x, dt, dA, B, C, interpret=True)
        want_y, want_s = ref.ssd_intra_ref(x, dt, dA, B, C)
        np.testing.assert_allclose(np.asarray(got_y, np.float32),
                                   np.asarray(want_y, np.float32), **TOL[dtype])
        np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                                   rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                                   atol=2e-2 if dtype == jnp.bfloat16 else 1e-4)


class TestSSDAgainstNaiveRecurrence:
    def test_chunked_equals_sequential(self):
        """models/ssm.ssd_chunked must equal the naive per-step recurrence
        h_t = exp(dA_t) h_{t-1} + dt_t B_t x_t^T ; y_t = C_t h_t + 0."""
        from repro.models.ssm import ssd_chunked

        b, s, h, p, g, n = 2, 64, 4, 16, 1, 24
        ks = jax.random.split(jax.random.PRNGKey(6), 5)
        x = _rand(ks[0], (b, s, h, p), jnp.float32)
        dt = jax.nn.softplus(_rand(ks[1], (b, s, h), jnp.float32))
        A = -jnp.abs(_rand(ks[2], (h,), jnp.float32)) * 0.5
        B = _rand(ks[3], (b, s, g, n), jnp.float32)
        C = _rand(ks[4], (b, s, g, n), jnp.float32)

        y_chunk, final_chunk = ssd_chunked(x, dt, A, B, C, chunk=16)

        # naive sequential reference
        state = np.zeros((b, h, p, n), np.float32)
        ys = []
        xn, dtn = np.asarray(x), np.asarray(dt)
        Bn = np.repeat(np.asarray(B), h // g, axis=2)
        Cn = np.repeat(np.asarray(C), h // g, axis=2)
        An = np.asarray(A)
        for t in range(s):
            dec = np.exp(dtn[:, t] * An)  # (b,h)
            upd = np.einsum("bh,bhp,bhn->bhpn", dtn[:, t], xn[:, t], Bn[:, t])
            state = dec[:, :, None, None] * state + upd
            ys.append(np.einsum("bhpn,bhn->bhp", state, Cn[:, t]))
        y_ref = np.stack(ys, axis=1)

        np.testing.assert_allclose(np.asarray(y_chunk), y_ref, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(final_chunk), state, rtol=2e-4,
                                   atol=2e-4)


class TestRMSNorm:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape", [(4, 16, 64), (2, 128), (1, 3, 7, 256)])
    def test_matches_ref(self, shape, dtype):
        from repro.kernels.rmsnorm import rmsnorm
        from repro.models.layers import rms_norm

        ks = jax.random.split(jax.random.PRNGKey(7), 2)
        x = _rand(ks[0], shape, dtype)
        w = _rand(ks[1], shape[-1:], dtype) * 0.1 + 1.0
        got = rmsnorm(x, w, eps=1e-5, block_rows=2, interpret=True)
        want = rms_norm(x, w, 1e-5)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **TOL[dtype])

    def test_odd_row_count(self):
        from repro.kernels.rmsnorm import rmsnorm
        from repro.models.layers import rms_norm

        x = _rand(jax.random.PRNGKey(8), (3, 5, 32), jnp.float32)
        w = jnp.ones((32,))
        got = rmsnorm(x, w, block_rows=4, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(rms_norm(x, w, 1e-5)),
                                   rtol=1e-5, atol=1e-5)
