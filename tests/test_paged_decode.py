"""Paged decode kernels (Pallas live-page loop + XLA fallback) vs the
dense/gathered oracles: ragged lengths, GQA, sliding window, softcap,
null-page masking, SPLS-compacted (pruned) layouts, and lengths around the
kernel's block edges."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, BlockCfg
from repro.kernels.paged_decode import (PAGES_PER_BLOCK, pages_per_block,
                                       pages_visited, paged_flash_decode)
from repro.kernels.ref import flash_decode_ref, paged_decode_ref
from repro.models import get_backend
from repro.serving.pager import POS_SENTINEL

jax.config.update("jax_platform_name", "cpu")


def _pool(B=3, KV=2, G=4, Dh=16, N=12, ps=8, P=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, KV, G, Dh))
    kp = jax.random.normal(ks[1], (KV, N, ps, Dh))
    vp = jax.random.normal(ks[2], (KV, N, ps, Dh))
    return q, kp, vp


def _contiguous_layout(tables, kv_len, N, ps):
    """pos_pages where slot index == original position (no pruning)."""
    pos = np.full((N, ps), POS_SENTINEL, np.int64)
    for b in range(tables.shape[0]):
        for j in range(tables.shape[1]):
            pg = int(tables[b, j])
            if pg == 0:
                continue
            pos[pg] = j * ps + np.arange(ps)
    return jnp.asarray(pos, jnp.int32)


class TestPagedKernelParity:
    """pallas_paged == xla gather oracle == contiguous dense oracle."""

    @pytest.mark.parametrize("window", [None, 5, 16])
    def test_ragged_gqa(self, window):
        B, KV, G, Dh, N, ps, P = 3, 2, 4, 16, 12, 8, 4
        q, kp, vp = _pool()
        tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]],
                             jnp.int32)
        kv_len = jnp.asarray([20, 9, 32], jnp.int32)
        pos = _contiguous_layout(np.asarray(tables), kv_len, N, ps)
        cur = kv_len - 1
        out = paged_flash_decode(q, kp, vp, pos, tables, kv_len, cur,
                                 window=window, interpret=True)
        want = paged_decode_ref(q, kp, vp, pos, tables, kv_len, cur,
                                window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5)
        # contiguous layout also matches the dense flash_decode oracle
        S = P * ps
        kd = jnp.moveaxis(kp[:, tables], 1, 0).reshape(B, KV, S, Dh)
        vd = jnp.moveaxis(vp[:, tables], 1, 0).reshape(B, KV, S, Dh)
        want2 = flash_decode_ref(q, kd, vd, cur, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want2),
                                   atol=2e-5)

    def test_softcap(self):
        q, kp, vp = _pool(seed=5)
        tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]],
                             jnp.int32)
        kv_len = jnp.asarray([17, 9, 25], jnp.int32)
        pos = _contiguous_layout(np.asarray(tables), kv_len, 12, 8)
        cur = kv_len - 1
        out = paged_flash_decode(q, kp, vp, pos, tables, kv_len, cur,
                                 softcap=30.0, interpret=True)
        want = paged_decode_ref(q, kp, vp, pos, tables, kv_len, cur,
                                softcap=30.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5)

    def test_null_page_and_garbage_masked(self):
        """Unwritten slots (incl. the whole null page) must not contribute,
        whatever garbage they hold."""
        q, kp, vp = _pool(seed=3)
        kp = kp.at[:, 0].set(1e6).at[:, 5].set(-1e6)  # null page + a dirty one
        vp = vp.at[:, 0].set(1e6).at[:, 5].set(-1e6)
        tables = jnp.asarray([[1, 2, 0, 0], [3, 4, 5, 0], [6, 7, 8, 9]],
                             jnp.int32)
        # row 1: page 5 allocated but only 1 slot written into it
        kv_len = jnp.asarray([11, 17, 32], jnp.int32)
        pos = _contiguous_layout(np.asarray(tables), kv_len, 12, 8)
        cur = kv_len - 1
        out = paged_flash_decode(q, kp, vp, pos, tables, kv_len, cur,
                                 interpret=True)
        assert np.isfinite(np.asarray(out)).all()
        want = paged_decode_ref(q, kp, vp, pos, tables, kv_len, cur)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5)
        # and dirty page 5's single written slot DOES contribute for row 1:
        # perturbing it must change row 1's output
        kp2 = kp.at[:, 5, 0].set(0.0)
        out2 = paged_flash_decode(q, kp2, vp, pos, tables, kv_len, cur,
                                  interpret=True)
        assert not np.allclose(np.asarray(out[1]), np.asarray(out2[1]))

    @pytest.mark.parametrize("window", [None, 6])
    def test_pruned_compacted_layout(self, window):
        """SPLS page pruning: slots hold a *subset* of positions; masks must
        use the original ids, matching a dense oracle with pruned columns
        masked out."""
        B, KV, G, Dh, N, ps = 2, 2, 3, 16, 10, 4
        ks = jax.random.split(jax.random.PRNGKey(9), 3)
        q = jax.random.normal(ks[0], (B, KV, G, Dh))
        L = 14  # original positions 0..13; keep a ragged subset per row
        keep = [np.asarray([0, 2, 3, 5, 8, 9, 12, 13]),
                np.asarray([1, 4, 6, 7, 10, 13])]
        kd = jax.random.normal(ks[1], (B, KV, L, Dh))
        vd = jax.random.normal(ks[2], (B, KV, L, Dh))
        P = 3
        tables = np.zeros((B, P), np.int64)
        kp = np.zeros((KV, N, ps, Dh), np.float32)
        vp = np.zeros((KV, N, ps, Dh), np.float32)
        pos = np.full((N, ps), POS_SENTINEL, np.int64)
        next_page = 1
        kv_len = []
        for b, idx in enumerate(keep):
            n = len(idx)
            kv_len.append(n)
            npages = -(-n // ps)
            pages = list(range(next_page, next_page + npages))
            next_page += npages
            tables[b, :npages] = pages
            for i, j in enumerate(idx):
                pg, off = pages[i // ps], i % ps
                kp[:, pg, off] = np.asarray(kd[b, :, j])
                vp[:, pg, off] = np.asarray(vd[b, :, j])
                pos[pg, off] = j
        tables = jnp.asarray(tables, jnp.int32)
        kv_len = jnp.asarray(kv_len, jnp.int32)
        posj = jnp.asarray(pos, jnp.int32)
        cur = jnp.asarray([L - 1, L - 1], jnp.int32)

        out = paged_flash_decode(q, jnp.asarray(kp), jnp.asarray(vp), posj,
                                 tables, kv_len, cur, window=window,
                                 interpret=True)
        # dense oracle: masked softmax over only the kept original columns
        Dh_s = Dh ** -0.5
        want = np.zeros((B, KV, G, Dh), np.float32)
        for b, idx in enumerate(keep):
            m = np.zeros((L,), bool)
            m[idx] = True
            if window is not None:
                m &= (L - 1) - np.arange(L) < window
            s = np.einsum("kgd,kld->kgl", np.asarray(q[b]),
                          np.asarray(kd[b])) * Dh_s
            s = np.where(m[None, None, :], s, -np.inf)
            a = np.exp(s - s.max(-1, keepdims=True))
            a = a / a.sum(-1, keepdims=True)
            want[b] = np.einsum("kgl,kld->kgd", a, np.asarray(vd[b]))
        np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)


def _xla(q, kp, vp, pos, tables, kv_len, cur, window=None):
    cfg = ArchConfig(period=(BlockCfg(),))
    return get_backend("xla_paged_decode")(
        cfg, q, kp, vp, pos_pages=pos, tables=tables, kv_len=kv_len, pos=cur,
        window=window)


def _shuffled_tables(B, P, npages, seed):
    """(B, P) tables of distinct shuffled pool pages, null past ``npages``
    of each row."""
    rng = np.random.default_rng(seed)
    perm = 1 + rng.permutation(B * P).reshape(B, P)
    return np.where(np.arange(P)[None, :] < np.asarray(npages)[:, None],
                    perm, 0)


class TestLivePageLoop:
    """The loop over blocks of ``pages_per_block`` pages: lengths at and
    around block edges, inactive rows, never-read pages, and a window in
    the compacted layout, each against ``xla_paged_decode``."""

    KV, G, Dh, ps = 2, 2, 16, 4
    P = 2 * PAGES_PER_BLOCK + PAGES_PER_BLOCK // 2  # 2.5 blocks a table

    def _pool(self, B, seed):
        N = B * self.P + 1
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (B, self.KV, self.G, self.Dh))
        kp = jax.random.normal(ks[1], (self.KV, N, self.ps, self.Dh))
        vp = jax.random.normal(ks[2], (self.KV, N, self.ps, self.Dh))
        return q, kp, vp, N

    @pytest.mark.parametrize("where", ["one", "block", "block+1", "2block",
                                       "2block+1", "max_len"])
    def test_lengths_at_block_edges(self, where):
        bk = pages_per_block(self.P) * self.ps
        n = {"one": 1, "block": bk, "block+1": bk + 1, "2block": 2 * bk,
             "2block+1": 2 * bk + 1, "max_len": self.P * self.ps}[where]
        B = 2
        q, kp, vp, N = self._pool(B, seed=21)
        kv_len = np.asarray([n, max(1, n - 3)])
        tables = jnp.asarray(_shuffled_tables(
            B, self.P, -(-kv_len // self.ps), seed=1), jnp.int32)
        kv_len = jnp.asarray(kv_len, jnp.int32)
        pos = _contiguous_layout(np.asarray(tables), kv_len, N, self.ps)
        cur = kv_len - 1
        out = paged_flash_decode(q, kp, vp, pos, tables, kv_len, cur,
                                 interpret=True)
        want = _xla(q, kp, vp, pos, tables, kv_len, cur)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5)

    def test_inactive_rows_beside_full_rows(self):
        """Rows the engine leaves inactive (all-null tables, one attended
        slot of the null page) mixed with rows at ``max_len``."""
        B = 4
        q, kp, vp, N = self._pool(B, seed=22)
        full = self.P * self.ps
        kv_len = np.asarray([full, 1, full, 1])
        npages = np.where(kv_len > 1, self.P, 0)
        tables = jnp.asarray(_shuffled_tables(B, self.P, npages, seed=2),
                             jnp.int32)
        kv_len = jnp.asarray(kv_len, jnp.int32)
        pos = _contiguous_layout(np.asarray(tables), kv_len, N, self.ps)
        cur = jnp.maximum(kv_len - 1, 0)
        out = paged_flash_decode(q, kp, vp, pos, tables, kv_len, cur,
                                 interpret=True)
        want = _xla(q, kp, vp, pos, tables, kv_len, cur)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5)

    @pytest.mark.parametrize("window", [None, 9])
    def test_pages_past_kv_len_are_never_read(self, window):
        """Every pool page no row attends (the null page, the pages a table
        holds past ``kv_len``, pages no table holds) and the unwritten tail
        of each last page hold NaN.  The output is finite and equals the
        reference on the same pool with those entries zeroed."""
        B = 3
        q, kp, vp, N = self._pool(B, seed=23)
        bk = pages_per_block(self.P) * self.ps
        kv_len = np.asarray([bk + 5, 5, 2 * bk])
        # allocated pages run two past the written ones (as if grown early)
        tables = _shuffled_tables(B, self.P, -(-kv_len // self.ps) + 2,
                                  seed=3)
        live = np.zeros((N, self.ps), bool)
        for b, n in enumerate(kv_len):
            for s in range(n):
                live[tables[b, s // self.ps], s % self.ps] = True
        tables = jnp.asarray(tables, jnp.int32)
        kv_len = jnp.asarray(kv_len, jnp.int32)
        pos = _contiguous_layout(np.asarray(tables), kv_len, N, self.ps)
        cur = kv_len - 1
        dead = jnp.asarray(~live)[None, :, :, None]
        out = paged_flash_decode(q, jnp.where(dead, jnp.nan, kp),
                                 jnp.where(dead, jnp.nan, vp), pos, tables,
                                 kv_len, cur, window=window, interpret=True)
        assert np.isfinite(np.asarray(out)).all()
        want = _xla(q, jnp.where(dead, 0.0, kp), jnp.where(dead, 0.0, vp),
                    pos, tables, kv_len, cur, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5)

    @pytest.mark.parametrize("edge", ["straddle", "skip"])
    def test_window_in_compacted_layout_across_blocks(self, edge):
        """SPLS-compacted rows (slot != position) over two blocks.  The
        window's live slots of row 0 start three slots before the first
        block edge (``straddle``), or two slots after it, so that every
        slot of row 0's first block is out and the block is skipped
        whole (``skip``)."""
        B = 2
        bk = pages_per_block(self.P) * self.ps
        L = 4 * bk
        q, kp0, vp0, N = self._pool(B, seed=24)
        rng = np.random.default_rng(4)
        keep = [np.sort(rng.choice(L, 2 * bk, replace=False)),
                np.sort(rng.choice(L, 2 * bk - 5, replace=False))]
        kd = np.asarray(jax.random.normal(jax.random.PRNGKey(5),
                                          (B, self.KV, L, self.Dh)))
        vd = np.asarray(jax.random.normal(jax.random.PRNGKey(6),
                                          (B, self.KV, L, self.Dh)))
        kp, vp = np.asarray(kp0).copy(), np.asarray(vp0).copy()
        pos = np.full((N, self.ps), POS_SENTINEL, np.int64)
        kv_len = np.asarray([len(k) for k in keep])
        tables = _shuffled_tables(B, self.P, -(-kv_len // self.ps), seed=5)
        cur = np.asarray([L - 1, L - 1])
        for b, idx in enumerate(keep):
            for i, j in enumerate(idx):
                pg, off = tables[b, i // self.ps], i % self.ps
                kp[:, pg, off] = kd[b, :, j]
                vp[:, pg, off] = vd[b, :, j]
                pos[pg, off] = j
        first = bk - 3 if edge == "straddle" else bk + 2
        window = int(cur[0] - keep[0][first]) + 1
        assert int(np.argmax(cur[0] - keep[0] < window)) == first
        args = (jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pos, jnp.int32),
                jnp.asarray(tables, jnp.int32), jnp.asarray(kv_len, jnp.int32),
                jnp.asarray(cur, jnp.int32))
        out = paged_flash_decode(q, *args, window=window, interpret=True)
        want = _xla(q, *args, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5)


class TestStackedPool:
    """Layer ``l`` of a stacked (L, KV, N, ps, Dh) pool, read through a
    traced layer index, is exactly the one-layer call on ``pool[l]``."""

    @pytest.mark.parametrize("G", [1, 2])
    @pytest.mark.parametrize("window", [None, 6])
    @pytest.mark.parametrize("backend", ["pallas_paged_decode",
                                         "xla_paged_decode"])
    def test_layer_of_stack_equals_its_slice(self, backend, window, G):
        L, B, KV, Dh, N, ps = 3, 3, 2, 16, 12, 8
        cfg = ArchConfig(period=(BlockCfg(),))
        ks = jax.random.split(jax.random.PRNGKey(21), 3)
        q = jax.random.normal(ks[0], (B, KV, G, Dh))
        kp = jax.random.normal(ks[1], (L, KV, N, ps, Dh))
        vp = jax.random.normal(ks[2], (L, KV, N, ps, Dh))
        tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]],
                             jnp.int32)
        kv_len = jnp.asarray([20, 9, 32], jnp.int32)
        kw = dict(pos_pages=_contiguous_layout(np.asarray(tables), kv_len,
                                               N, ps),
                  tables=tables, kv_len=kv_len, pos=kv_len - 1,
                  window=window)
        if backend == "pallas_paged_decode":
            def fn(cfg, q, k, v, layer=None, window=None, **kw):
                return paged_flash_decode(
                    q, k, v, kw["pos_pages"], kw["tables"], kw["kv_len"],
                    kw["pos"], layer=layer, window=window, interpret=True)
        else:
            fn = get_backend(backend)
        stacked = jax.jit(lambda l: fn(cfg, q, kp, vp, layer=l, **kw))
        for l in range(L):
            got = stacked(jnp.int32(l))
            want = fn(cfg, q, kp[l], vp[l], **kw)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_layer_index_goes_with_a_stacked_pool(self):
        q, kp, vp = _pool()
        tables = jnp.ones((3, 4), jnp.int32)
        n = jnp.ones((3,), jnp.int32)
        pos = jnp.zeros((12, 8), jnp.int32)
        with pytest.raises(ValueError):
            paged_flash_decode(q, kp, vp, pos, tables, n, n,
                               layer=jnp.int32(0), interpret=True)
        with pytest.raises(ValueError):
            paged_flash_decode(q, kp[None], vp[None], pos, tables, n, n,
                               interpret=True)


class TestPagesVisited:
    def test_counts_pages_holding_attended_slots(self):
        assert pages_visited(1, 16) == 1
        assert pages_visited(16, 16) == 1
        assert pages_visited(17, 16) == 2
        assert pages_visited(np.asarray([1, 1, 2048, 300]), 16) == 1 + 1 + \
            128 + 19

    def test_block_is_a_constant_capped_by_the_table(self):
        assert pages_per_block(128) == PAGES_PER_BLOCK
        assert pages_per_block(2) == 2


class TestPagedBackendRegistry:
    def test_backends_registered_and_agree(self):
        from repro.models import available_backends, resolve_backend
        assert "xla_paged_decode" in available_backends(decode=True,
                                                        paged=True)
        assert "pallas_paged_decode" in available_backends(decode=True,
                                                           paged=True)
        # auto resolution at a paged decode site
        cfg = ArchConfig(period=(BlockCfg(),))
        got = resolve_backend("auto", cfg, L=64, decode=True, paged=True,
                              platform="cpu")
        assert got == "xla_paged_decode"
        got = resolve_backend("auto", cfg, L=64, decode=True, paged=True,
                              platform="tpu")
        assert got == "pallas_paged_decode"
        # a non-paged decode name at a paged site falls through to auto
        got = resolve_backend("pallas_flash_decode", cfg, L=64, decode=True,
                              paged=True, platform="cpu")
        assert got == "xla_paged_decode"

    def test_backend_fns_agree(self):
        cfg = ArchConfig(period=(BlockCfg(),))
        q, kp, vp = _pool(seed=11)
        tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]],
                             jnp.int32)
        kv_len = jnp.asarray([20, 9, 32], jnp.int32)
        pos = _contiguous_layout(np.asarray(tables), kv_len, 12, 8)
        cur = kv_len - 1
        kw = dict(pos_pages=pos, tables=tables, kv_len=kv_len, pos=cur,
                  window=7)
        a = get_backend("xla_paged_decode")(cfg, q, kp, vp, **kw)
        b = get_backend("pallas_paged_decode")(cfg, q, kp, vp, **kw)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
