"""Launch-layer units: HLO collective parser, mesh builders, input specs."""
import jax
import numpy as np
import pytest

import repro.configs as C


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %ag = f32[16,1152]{1,0} all-gather(%x), replica_groups=...
  %ar.1 = bf16[8,256,4608]{2,1,0} all-reduce(%y), to_apply=%add
  %rs = (f32[4,4]{1,0}, f32[2,2]{1,0}) reduce-scatter(%a, %b), dims={0}
  %ag2 = f32[32]{0} all-gather-start(%z), dims={0}
  %done = f32[32]{0} all-gather-done(%ag2)
  %cp = u8[128]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %a2a = s32[64,2]{1,0} all-to-all(%v), dimensions={0}
"""
    got = collective_bytes(hlo)
    assert got["all-gather"] == 16 * 1152 * 4 + 32 * 4
    assert got["all-reduce"] == 8 * 256 * 4608 * 2
    assert got["reduce-scatter"] == 16 * 4 + 4 * 4
    assert got["collective-permute"] == 128
    assert got["all-to-all"] == 64 * 2 * 4


def test_input_specs_all_cells():
    """batch_specs/decode_specs build for every assignment cell without
    allocation and with assignment-correct shapes."""
    from repro.launch import specs as SP
    for arch, shape in C.cells():
        c = SP.cell(arch, shape)
        if c.step_kind in ("train", "prefill"):
            b = SP.batch_specs(c)
            tot = b["tokens"].shape[1] + (c.cfg.prefix_len or 0)
            assert b["tokens"].shape[0] == c.global_batch
            assert tot == c.seq_len
        else:
            d = SP.decode_specs(c)
            assert d["tokens"].shape == (c.global_batch, 1)
            # cache capacity == seq_len for full-attention slots
            leaves = jax.tree.leaves(d["cache"])
            assert all(x.shape[1] == c.global_batch for x in leaves)


def test_cell_table_is_the_assignment():
    cells = C.cells(include_skipped=True)
    assert len(cells) == len(C.ARCHS) * len(C.SHAPES)
    skipped = {(a, s) for a, s, sk in cells if sk}
    assert all(s == "long_500k" for _, s in skipped)
    # one skipped long_500k cell per arch lacking long-context support
    assert len(skipped) == sum(not C.get(a).supports_long_context
                               for a in C.ARCHS)


def test_host_mesh_shapes():
    from repro.launch.mesh import make_host_mesh
    m = make_host_mesh(data=1, model=1)
    assert m.axis_names == ("data", "model")


def test_train_overrides_cover_heavy_archs():
    from repro.launch.dryrun import TRAIN_OVERRIDES
    assert TRAIN_OVERRIDES["nemotron-4-340b"]["state_dtype"] == "bfloat16"
    assert TRAIN_OVERRIDES["deepseek-v3-671b"]["accum"] >= 4


def test_compile_cache_defaults_to_a_fixed_dir_in_the_checkout(monkeypatch):
    from repro import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable()
        assert path == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.DEFAULT_DIR.parent.joinpath(
            "src", "repro", "compile_cache.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_var_wins_and_nothing_else_is_set(monkeypatch,
                                                           tmp_path):
    from repro import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
