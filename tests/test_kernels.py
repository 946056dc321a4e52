"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps +
hypothesis property tests."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FA_SHAPES = [(1, 128, 1, 32), (2, 256, 4, 64), (1, 512, 2, 128)]


@pytest.mark.parametrize("shape", FA_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(shape, dtype):
    B, S, H, D = shape
    rng = np.random.default_rng(42)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), dtype) for _ in range(3))
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    want = ref.ref_attention(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [64, 128])
def test_flash_attention_sliding_window(window):
    B, S, H, D = 1, 256, 2, 64
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
               for _ in range(3))
    out = ops.flash_attention(q, k, v, window=window, block_q=64, block_k=64)
    want = ref.ref_attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_softcap_and_noncausal():
    B, S, H, D = 1, 128, 2, 32
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
               for _ in range(3))
    out = ops.flash_attention(q, k, v, softcap=50.0, block_q=64, block_k=64)
    want = ref.ref_attention(q, k, v, softcap=50.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    out_nc = ops.flash_attention(q, k, v, causal=False, block_q=64,
                                 block_k=64)
    want_nc = ref.ref_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out_nc), np.asarray(want_nc),
                               rtol=2e-3, atol=2e-3)


def test_flash_matches_model_attention_path():
    """The kernel agrees with the model-side XLA attention (attn_apply)."""
    from repro.configs import smoke_config
    from repro.models import attention, layers

    cfg = smoke_config("gemma2-27b")
    B, S = 1, 64
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((B, S, cfg.d_model)), jnp.float32)
    specs = attention.attn_specs(cfg)
    params = layers.init_params(jax.random.PRNGKey(0), specs, jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    xla_out, _ = attention.attn_apply(params["attn"], x, cfg, "attn", pos,
                                      lambda t, a: t, impl="xla")
    import dataclasses

    cfg_p = dataclasses.replace(cfg, attention_impl="pallas")
    pl_out, _ = attention.attn_apply(params["attn"], x, cfg_p, "attn", pos,
                                     lambda t, a: t, impl="pallas")
    np.testing.assert_allclose(np.asarray(xla_out), np.asarray(pl_out),
                               rtol=3e-3, atol=3e-3)


# ---------------------------------------------------------------------------
# groupby
# ---------------------------------------------------------------------------


@given(st.integers(10, 3000), st.integers(1, 200),
       st.sampled_from(["sum", "count", "mean", "min", "max"]))
@settings(max_examples=20, deadline=None)
def test_groupby_matches_ref(n, g, fn):
    rng = np.random.default_rng(n * 31 + g)
    vals = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    codes = jnp.asarray(rng.integers(0, g, n).astype(np.int32))
    out = ops.groupby_aggregate(vals, codes, g, fn)
    want = ref.ref_groupby(vals, codes, g, fn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_groupby_rejects_more_groups_than_vmem_holds():
    vals = jnp.ones(8, jnp.float32)
    codes = jnp.zeros(8, jnp.int32)
    with pytest.raises(ValueError, match="MAX_GROUPS"):
        ops.groupby_aggregate(vals, codes, ops.MAX_GROUPS + 1)


def test_groupby_empty_groups():
    vals = jnp.asarray(np.ones(64, np.float32))
    codes = jnp.asarray(np.zeros(64, np.int32))
    out = ops.groupby_aggregate(vals, codes, 5, "sum")
    np.testing.assert_allclose(np.asarray(out), [64, 0, 0, 0, 0])


# ---------------------------------------------------------------------------
# filter compaction
# ---------------------------------------------------------------------------


@given(st.integers(1, 5000), st.floats(0.0, 1.0))
@settings(max_examples=25, deadline=None)
def test_compact_matches_nonzero(n, p):
    rng = np.random.default_rng(int(n * 1000 * (p + 1)))
    mask = jnp.asarray(rng.random(n) < p)
    idx, cnt = ops.compact(mask)
    want = np.nonzero(np.asarray(mask))[0]
    assert int(cnt) == len(want)
    np.testing.assert_array_equal(np.asarray(idx)[:int(cnt)], want)


def test_compact_all_and_none():
    mask = jnp.asarray(np.ones(512, bool))
    idx, cnt = ops.compact(mask)
    assert int(cnt) == 512
    np.testing.assert_array_equal(np.asarray(idx), np.arange(512))
    mask0 = jnp.asarray(np.zeros(512, bool))
    _, cnt0 = ops.compact(mask0)
    assert int(cnt0) == 0


@pytest.mark.parametrize("fn", ["sum", "count", "mean", "min", "max"])
@pytest.mark.parametrize("n,g", [(1, 1), (1500, 6), (3000, 128)])
def test_groupby_rows_matches_ref(n, g, fn):
    """Host entry point: padded rows and groups reach no real group, also
    when the group count is already a lane multiple."""
    rng = np.random.default_rng(n + g)
    vals = rng.standard_normal(n)
    codes = rng.integers(0, g, n)
    out = ops.groupby_aggregate_rows(vals, codes, g, fn)
    want = ref.ref_groupby(jnp.asarray(vals, jnp.float32),
                           jnp.asarray(codes, jnp.int32), g, fn)
    assert out.shape == (g,)
    np.testing.assert_allclose(out, np.asarray(want), rtol=1e-4, atol=1e-4)


def test_host_entry_points_compile_per_bucket_not_per_length():
    """Chunks of a streamed scan differ in length; the host entry points pad
    them to a power-of-two bucket, so 40 lengths in (1024, 2048] compile one
    program per kernel wrapper."""
    rng = np.random.default_rng(3)
    lengths = range(1100, 1140)
    compact0 = ops.compact._cache_size()
    groupby0 = ops.groupby_aggregate._cache_size()
    for n in lengths:
        mask = rng.random(n) < 0.3
        np.testing.assert_array_equal(ops.compact_indices(mask),
                                      np.nonzero(mask)[0])
        ops.groupby_aggregate_rows(np.ones(n), rng.integers(0, 6, n), 6)
    assert ops.compact._cache_size() - compact0 <= 1
    assert ops.groupby_aggregate._cache_size() - groupby0 <= 1


def test_compute_jax_backend_routes_through_kernels(lakehouse):
    """columnar.compute backend='jax' uses the Pallas-backed ops."""
    from repro.columnar import compute

    catalog, _ = lakehouse
    t = catalog.read_table("transactions",
                           columns=["usd", "country", "eventTime"])
    a = compute.filter_table(t, "usd > 100", backend="jax")
    b = compute.filter_table(t, "usd > 100", backend="numpy")
    assert a.equals(b)
    ga = compute.group_by(a, ["country"], {"s": ("usd", "sum")},
                          backend="jax")
    gb = compute.group_by(a, ["country"], {"s": ("usd", "sum")},
                          backend="numpy")
    np.testing.assert_allclose(ga.column("s").to_numpy(),
                               gb.column("s").to_numpy(), rtol=1e-6)


# ---------------------------------------------------------------------------
# device choice
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("platform,tpu_error,expect", [
    ("tpu", None, False),
    ("cpu", "Unknown backend tpu. Available backends are ['cpu']", True),
    ("cpu", "Backend 'tpu' failed to initialize: TPU is busy", RuntimeError),
    ("gpu", None, RuntimeError),
])
def test_interpret_mode_only_on_the_cpu_platform(monkeypatch, platform,
                                                 tpu_error, expect):
    """Interpret mode is chosen on the CPU platform only, and never for a
    process that fell back to the CPU because it could not open the TPU."""
    def devices(backend=None):
        if tpu_error:
            raise RuntimeError(tpu_error)
        return []

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    monkeypatch.setattr(jax, "devices", devices)
    if expect is RuntimeError:
        with pytest.raises(RuntimeError):
            ops._interpret()
    else:
        assert ops._interpret() is expect


def test_import_repro_loads_no_jax_and_sets_no_cache():
    """A process that only imports the package leaves the chip and the
    compile cache alone: both are the entry points' business."""
    import subprocess
    import sys

    code = ("import sys, repro; "
            "assert 'jax' not in sys.modules, 'import repro loaded jax'")
    subprocess.run([sys.executable, "-c", code], check=True)


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert updates == []        # JAX reads the variable itself
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        where = compile_cache.enable_compile_cache()
        assert updates == [("jax_compilation_cache_dir", where)]
        # one fixed directory at the checkout's root, never committed
        root = compile_cache.CACHE_DIR.parent
        assert where == str(compile_cache.CACHE_DIR)
        assert (root / "src" / "repro").is_dir()
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
