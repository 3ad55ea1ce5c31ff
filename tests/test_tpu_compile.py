"""Real-size compiles of the served path for a described TPU v5e.

No chip is attached: the TPU compiler installed with JAX compiles for a
described topology and refuses what the chip would refuse -- block tiling,
fast-memory limits, a program that does not fit the device's memory --
which interpret mode cannot show.  The topology is described only inside
the module fixture below, so importing this file loads no TPU library.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as C
from repro.kernels import ops
from repro.models import model as mdl
from repro.serve import sched as S


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host, with JAX's persistent cache
    off: a compile for a described chip is written to the cache but cannot
    be read back without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("q_dtype,page_dtype", [
    (jnp.float32, jnp.float32), (jnp.bfloat16, jnp.float32),
    (jnp.bfloat16, jnp.bfloat16)])
def test_paged_attention_compiles_at_qwen3_14b_width(one_chip, q_dtype,
                                                     page_dtype):
    cfg = C.get("qwen3-14b")
    b, page, cols = 8, 16, 128
    h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    fn = jax.jit(lambda q, k, v, t, n: ops.paged_attention(
        q, k, v, t, n, return_mass=True, impl="pallas"))
    compiled = fn.lower(
        _sds((b, h, d), q_dtype, one_chip),
        _sds((b * cols, page, kvh, d), page_dtype, one_chip),
        _sds((b * cols, page, kvh, d), page_dtype, one_chip),
        _sds((b, cols), jnp.int32, one_chip),
        _sds((b,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("heads", [10, 8])       # qwen3-14b-tp4, qwen3-8b-tp4
@pytest.mark.parametrize("window", [0, 1024])
def test_paged_attention_compiles_at_the_served_shape(one_chip, heads,
                                                      window):
    """The shape the benchmark cells serve: 8 rows of 68 table pages of 64
    positions over an f32 pool of 640 slots, 2 KV heads of 128.  The walk
    over live blocks of pages stays one kernel: one custom call, no XLA
    pass over the pool around it."""
    b, cols, page, slots, kvh, d = 8, 68, 64, 640, 2, 128
    fn = jax.jit(lambda q, k, v, t, n: ops.paged_attention(
        q, k, v, t, n, window=window, return_mass=True, impl="pallas"))
    compiled = fn.lower(
        _sds((b, heads, d), jnp.float32, one_chip),
        _sds((slots, page, kvh, d), jnp.float32, one_chip),
        _sds((slots, page, kvh, d), jnp.float32, one_chip),
        _sds((b, cols), jnp.int32, one_chip),
        _sds((b,), jnp.int32, one_chip)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_mla_compiles_at_deepseek_v3_width(one_chip, dtype):
    cfg = C.get("deepseek-v3-671b")
    m = cfg.mla
    b, page, cols = 8, 16, 128
    scale = 1.0 / (m.qk_nope_dim + m.qk_rope_dim) ** 0.5
    fn = jax.jit(lambda a, r, c, k, t, n: ops.paged_attention_mla(
        a, r, c, k, t, n, scale=scale, return_mass=True, impl="pallas"))
    compiled = fn.lower(
        _sds((b, cfg.num_heads, m.kv_lora_rank), dtype, one_chip),
        _sds((b, cfg.num_heads, m.qk_rope_dim), dtype, one_chip),
        _sds((b * cols, page, m.kv_lora_rank), dtype, one_chip),
        _sds((b * cols, page, m.qk_rope_dim), dtype, one_chip),
        _sds((b, cols), jnp.int32, one_chip),
        _sds((b,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows,sizes", [(64, (1, 1, 0, 0, 0, 0, 0, 0)),
                                        (16384, (64,) * 8)])
def test_expert_gmm_compiles_at_deepseek_v3_width(one_chip, rows, sizes):
    """The expert layer's grouped matmul at DeepSeek-V3's widths over 8
    held experts, at a decode step's buffer (8 rows x top 8) and a prefill
    chunk's: gate/up (7168 -> 2048) and down (2048 -> 7168)."""
    cfg = C.get("deepseek-v3-671b")
    d, f = cfg.d_model, cfg.moe.d_expert
    for k, n in ((d, f), (f, d)):
        fn = jax.jit(lambda x, w, g: ops.expert_gmm(x, w, g, impl="pallas"))
        compiled = fn.lower(_sds((rows, k), jnp.bfloat16, one_chip),
                            _sds((len(sizes), k, n), jnp.bfloat16, one_chip),
                            _sds((len(sizes),), jnp.int32,
                                 one_chip)).compile()
        assert "tpu_custom_call" in compiled.as_text()


def test_served_macro_program_fits_one_v5e(one_chip):
    """The macro decode program ``chip_smoke.py`` serves -- qwen3-14b at
    published widths cut to 2 layers, 4 rows of 2112 positions, page 16 --
    compiles for one v5e with the kernel inside and fits its memory (the
    compiler raises when it does not)."""
    cfg = dataclasses.replace(C.get("qwen3-14b"),
                              segments=((("attn",), 2),))
    rows, page, n_row = 4, 16, 2112 // 16
    hbm, n_logical = rows * n_row, 2 * rows * n_row
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: mdl.init(jax.random.PRNGKey(0), cfg)[0]))
    trail = (page, cfg.num_kv_heads, cfg.head_dim)
    kv = {f"{leaf}_{tier}": [_sds((2, n) + trail, jnp.float32, one_chip)]
          for leaf in ("k", "v")
          for tier, n in (("hbm", hbm), ("host", n_logical))}
    i32 = lambda *s: _sds(s, jnp.int32, one_chip)
    compiled = S.decode_macro.lower(
        params, cfg, kv, i32(rows, n_row), i32(rows, n_row), i32(rows, 1),
        i32(rows), _sds((rows, 2), jnp.uint32, one_chip), i32(rows),
        i32(rows), i32(rows), i32(rows), _sds((rows,), jnp.float32, one_chip),
        n_steps=16, page_size=page, impl="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 15.75 * 2 ** 30
