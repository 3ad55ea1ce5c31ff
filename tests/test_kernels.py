"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps.

Property-style coverage runs as deterministic ``pytest.mark.parametrize``
cases over seeded random inputs (no optional ``hypothesis`` dependency)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# page_hist
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("num_pages,tile_accesses", [(512, 100), (1024, 1000),
                                                     (2048, 4096)])
def test_page_hist_matches_ref(num_pages, tile_accesses):
    key = jax.random.PRNGKey(num_pages)
    ids = jax.random.randint(key, (tile_accesses,), 0, num_pages, jnp.int32)
    hot = jax.random.uniform(jax.random.PRNGKey(1), (num_pages,)) * 3
    for alpha, thr in [(0.5, 1.0), (0.9, 0.5)]:
        c1, h1, m1 = ops.page_hist(ids, hot, alpha=alpha, threshold=thr,
                                   impl="interpret")
        c2, h2, m2 = ref.page_hist_ref(ids, hot, alpha=alpha, threshold=thr)
        np.testing.assert_allclose(c1, c2, atol=1e-6)
        np.testing.assert_allclose(h1, h2, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))


def test_page_hist_padding_ignored():
    ids = jnp.array([3, 3, -1, -1, 7], jnp.int32)
    hot = jnp.zeros((512,))
    c, h, m = ops.page_hist(ids, hot, impl="interpret")
    assert float(c[3]) == 2.0 and float(c[7]) == 1.0
    assert float(c.sum()) == 3.0


@pytest.mark.parametrize("seed", range(10))
def test_page_hist_property(seed):
    rng = np.random.default_rng(seed)
    num_pages = 512
    n = int(rng.integers(10, 400))
    ids = jnp.asarray(rng.integers(0, num_pages, n), jnp.int32)
    hot = jnp.asarray(rng.random(num_pages), jnp.float32)
    c, h, m = ops.page_hist(ids, hot, impl="interpret")
    assert float(c.sum()) == n                       # counts conserve accesses
    c2, h2, m2 = ref.page_hist_ref(ids, hot)
    np.testing.assert_allclose(c, c2, atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,h,kv,d", [(256, 4, 4, 64), (512, 4, 2, 64),
                                      (256, 8, 1, 128)])
def test_flash_attention_matches_ref(s, h, kv, d, dtype):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, s, h, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (2, s, kv, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (2, s, kv, d), dtype)
    o = ops.flash_attention(q, k, v, bq=128, bkv=128, impl="interpret")
    r = ref.flash_attention_ref(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=tol)


def test_flash_attention_sliding_window():
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 256, 4, 64))
    k = jax.random.normal(jax.random.PRNGKey(4), (1, 256, 4, 64))
    v = jax.random.normal(jax.random.PRNGKey(5), (1, 256, 4, 64))
    o = ops.flash_attention(q, k, v, window=64, bq=64, bkv=64,
                            impl="interpret")
    r = ref.flash_attention_ref(q, k, v, window=64)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


def test_flash_attention_noncausal():
    q = jax.random.normal(jax.random.PRNGKey(6), (1, 128, 2, 64))
    k = jax.random.normal(jax.random.PRNGKey(7), (1, 128, 2, 64))
    v = jax.random.normal(jax.random.PRNGKey(8), (1, 128, 2, 64))
    o = ops.flash_attention(q, k, v, causal=False, bq=64, bkv=64,
                            impl="interpret")
    r = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------


# Walks over more than one block of pages per row.  At page 64, 2 KV heads
# and head dim 128 the kernel gathers 8 f32 (16 bf16) pages a grid step,
# so a 20-page table ends in a partial block; 4 and 1 KV heads give 4 and
# 16 f32 pages a block.
WALK_PAGES, WALK_PAGE, WALK_D, WALK_PHYS = 20, 64, 128, 64
WALKS = {
    "len0": [0, 20 * 64, 700],            # an idle row next to full ones
    "len1": [1, 20 * 64, 65],
    "block-edge": [512, 513, 1024],       # 512 = 8 pages, 1024 = 16 pages
    "window-skip": [20 * 64, 1000, 700],  # a window of 200 skips blocks
    "ragged": [700, 20 * 64, 130],        # table -1 past each row's pages
    # an idle row between live ones: the last live block of row 0 starts
    # row 2's first block, across the idle row, into the other buffer slot
    "len0-mid": [700, 0, 20 * 64],
    "len0-ends": [0, 900, 0],             # the first live block is row 1's
    # at a window of 200 row 2 attends 800-999, so its first live block is
    # block 1, and row 0's only live block (1080-1279) must start it
    "len0-mid-late": [20 * 64, 0, 1000],
}


def _walk_inputs(walk, h, kv, dtype):
    lengths = WALKS[walk]
    b = len(lengths)
    key = jax.random.PRNGKey(len(walk) * 10 + kv)
    q = jax.random.normal(key, (b, h, WALK_D), dtype)
    shape = (WALK_PHYS, WALK_PAGE, kv, WALK_D)
    kp = jax.random.normal(jax.random.fold_in(key, 1), shape, dtype)
    vp = jax.random.normal(jax.random.fold_in(key, 2), shape, dtype)
    pt = np.array(jax.random.permutation(jax.random.fold_in(key, 3),
                                          WALK_PHYS)[:b * WALK_PAGES]
                   ).reshape(b, WALK_PAGES)
    if walk == "ragged":
        for r, n in enumerate(lengths):
            pt[r, -(-n // WALK_PAGE):] = -1
    return q, kp, vp, jnp.asarray(pt, jnp.int32), jnp.asarray(lengths,
                                                              jnp.int32)


def _assert_rows_match(got, want, lengths, atol):
    """Rows that attend something match the oracle; a row of length 0
    attends nothing and the kernel writes zeros there."""
    got = np.asarray(got, np.float32)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], np.asarray(want, np.float32)[live],
                               atol=atol)
    np.testing.assert_array_equal(got[~live], 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,kv,d,page,walk", [
    pytest.param(4, 4, 64, 16, None, id="4-4-64-16"),
    pytest.param(8, 2, 64, 32, None, id="8-2-64-32"),
    pytest.param(8, 1, 128, 16, None, id="8-1-128-16"),
    *[pytest.param(8, 2, WALK_D, WALK_PAGE, w, id=f"walk-{w}")
      for w in ("len0", "len1", "block-edge", "ragged", "len0-mid",
                "len0-ends")]])
def test_paged_attention_matches_ref(h, kv, d, page, walk, dtype):
    if walk is None:
        b, n_pages, p_phys = 3, 8, 64
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (b, h, d), dtype)
        kp = jax.random.normal(jax.random.PRNGKey(1), (p_phys, page, kv, d),
                               dtype)
        vp = jax.random.normal(jax.random.PRNGKey(2), (p_phys, page, kv, d),
                               dtype)
        pt = jax.random.permutation(
            jax.random.PRNGKey(3), p_phys)[: b * n_pages].reshape(b, n_pages)
        lengths = jnp.array([n_pages * page, n_pages * page - 7, page + 3],
                            jnp.int32)
    else:
        q, kp, vp, pt, lengths = _walk_inputs(walk, h, kv, dtype)
    o = ops.paged_attention(q, kp, vp, pt, lengths, impl="interpret")
    r = ops.paged_attention(q, kp, vp, pt, lengths, impl="reference")
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    _assert_rows_match(o, r, lengths, tol)


@pytest.mark.parametrize("h,kv", [(4, 4), (8, 2), (8, 1)])  # GQA ratios
@pytest.mark.parametrize("window,softcap,walk", [
    pytest.param(0, 0.0, None, id="0-0.0"),
    pytest.param(3, 0.0, None, id="3-0.0"),
    pytest.param(0, 5.0, None, id="0-5.0"),
    pytest.param(8, 5.0, None, id="8-5.0"),
    pytest.param(0, 0.0, "len0", id="0-0.0-walk-len0"),
    pytest.param(0, 5.0, "len1", id="0-5.0-walk-len1"),
    pytest.param(0, 0.0, "block-edge", id="0-0.0-walk-block-edge"),
    pytest.param(200, 0.0, "window-skip", id="200-0.0-walk-window-skip"),
    pytest.param(200, 5.0, "ragged", id="200-5.0-walk-ragged"),
    pytest.param(0, 0.0, "len0-mid", id="0-0.0-walk-len0-mid"),
    pytest.param(0, 5.0, "len0-ends", id="0-5.0-walk-len0-ends"),
    pytest.param(200, 0.0, "len0-mid-late", id="200-0.0-walk-len0-mid-late")])
def test_paged_attention_kernel_mass_matches_oracle(h, kv, window, softcap,
                                                    walk):
    """The mass emitted from the kernel's own online-softmax accumulators
    (the fused telemetry output) equals the reference oracle's per-page
    attention-probability mass -- across sliding windows, tanh softcap and
    every GQA ratio, including ragged -1-padded tables, and over walks of
    several blocks of pages that skip what a row does not attend."""
    if walk is None:
        b, n_pages, p_phys, page, d = 3, 5, 24, 4, 16
        key = jax.random.PRNGKey(h * 100 + window)
        q = jax.random.normal(key, (b, h, d))
        kp = jax.random.normal(jax.random.fold_in(key, 1),
                               (p_phys, page, kv, d))
        vp = jax.random.normal(jax.random.fold_in(key, 2),
                               (p_phys, page, kv, d))
        pt = jnp.asarray([[2, 7, 11, 3, 9],
                          [5, 1, 20, -1, -1],          # ragged short row
                          [8, 4, 6, 12, 17]], jnp.int32)
        lengths = jnp.asarray([n_pages * page - 2, 3 * page - 1,
                               2 * page + 3], jnp.int32)
    else:
        q, kp, vp, pt, lengths = _walk_inputs(walk, h, kv, jnp.float32)
    out, mass = ops.paged_attention(q, kp, vp, pt, lengths, window=window,
                                    softcap=softcap, return_mass=True,
                                    impl="interpret")
    ref_o, ref_m = ops.paged_attention(q, kp, vp, pt, lengths, window=window,
                                       softcap=softcap, return_mass=True,
                                       impl="reference")
    _assert_rows_match(out, ref_o, lengths, 1e-5)
    _assert_rows_match(mass, ref_m, lengths, 1e-5)
    # head-normalised: every in-length row's mass sums to ~1
    np.testing.assert_allclose(np.asarray(mass).sum(axis=1),
                               (np.asarray(lengths) > 0).astype(np.float32),
                               atol=1e-5)


@pytest.mark.parametrize("scheduler", ["reactive", "predictive"])
def test_sim_scan_pallas_matches_jax_bitwise(scheduler):
    """The fused ``kernels.sim_step`` sweep (rank-based top-k selection in
    VMEM scratch) is bit-identical to the vmapped lax.scan path."""
    from repro.core import sim, traces

    rng = np.random.default_rng(7)
    tr = traces.Trace("toy", rng.integers(0, 20, 3000).astype(np.int64), 20,
                      np.asarray([50]))
    bins = sim.bin_trace(tr, block=50)
    a = sim.sweep(bins, [100, 250, 600, 1500], scheduler=scheduler)
    b = sim.sweep(bins, [100, 250, 600, 1500], scheduler=scheduler,
                  impl="interpret")
    assert set(a) == set(b)
    for k in a:
        assert a[k].runtime == b[k].runtime
        assert a[k].migrations == b[k].migrations
        assert a[k].fast_hits == b[k].fast_hits


def test_paged_attention_page_permutation_invariance():
    """Physically permuting pages (with the table updated) cannot change the
    output -- the invariant the tiering runtime relies on when it migrates
    pages between tiers."""
    b, h, kv, d, page, n_pages, p_phys = 2, 4, 2, 64, 16, 4, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, d))
    kp = jax.random.normal(jax.random.PRNGKey(1), (p_phys, page, kv, d))
    vp = jax.random.normal(jax.random.PRNGKey(2), (p_phys, page, kv, d))
    pt = jnp.arange(b * n_pages, dtype=jnp.int32).reshape(b, n_pages)
    lengths = jnp.full((b,), n_pages * page, jnp.int32)
    o1 = ops.paged_attention(q, kp, vp, pt, lengths, impl="interpret")
    perm = jax.random.permutation(jax.random.PRNGKey(3), p_phys)
    inv = jnp.argsort(perm)
    o2 = ops.paged_attention(q, kp[perm], vp[perm], inv[pt], lengths,
                             impl="interpret")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)


# ---------------------------------------------------------------------------
# kernel path chosen from the platform
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,cpu,want", [
    ("tpu", "interpret", "pallas"), ("tpu", "reference", "pallas"),
    ("cpu", "interpret", "interpret"), ("cpu", "reference", "reference")])
def test_default_impl_follows_the_platform(monkeypatch, backend, cpu, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.default_impl(cpu) == want


def test_default_impl_refuses_an_unknown_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(ValueError, match="no kernel path"):
        ops.default_impl("interpret")
