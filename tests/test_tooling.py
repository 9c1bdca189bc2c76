"""Tests for the analysis/launch tooling itself: the trip-count-aware HLO
parser (roofline source of truth) and the sharding-spec recipes."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "benchmarks"))

from hlo_analysis import analyze, shape_bytes, shape_dims  # noqa: E402


SYNTH_HLO = """\
HloModule test

%fused_computation (param_0: f32[128,64]) -> f32[128,64] {
  %param_0 = f32[128,64]{1,0} parameter(0)
  ROOT %exp = f32[128,64]{1,0} exponential(%param_0)
}

%body (arg: (s32[], f32[128,64], f32[64,32])) -> (s32[], f32[128,64], f32[64,32]) {
  %arg = (s32[], f32[128,64]{1,0}, f32[64,32]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[128,64]{1,0} get-tuple-element(%arg), index=1
  %w = f32[64,32]{1,0} get-tuple-element(%arg), index=2
  %dot.1 = f32[128,32]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ag = f32[64,32]{1,0} all-gather(%w), channel_id=1, replica_groups={{0,1}}, dimensions={0}
  %fus = f32[128,64]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation
  ROOT %out = (s32[], f32[128,64]{1,0}, f32[64,32]{1,0}) tuple(%i, %fus, %ag)
}

%cond (arg: (s32[], f32[128,64], f32[64,32])) -> pred[] {
  %arg = (s32[], f32[128,64]{1,0}, f32[64,32]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (a: f32[128,64], b: f32[64,32]) -> f32[128,32] {
  %a = f32[128,64]{1,0} parameter(0)
  %b = f32[64,32]{1,0} parameter(1)
  %t = (s32[], f32[128,64]{1,0}, f32[64,32]{1,0}) tuple(%a, %a, %b)
  %wh = (s32[], f32[128,64]{1,0}, f32[64,32]{1,0}) while(%t), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  %x2 = f32[128,64]{1,0} get-tuple-element(%wh), index=1
  %w2 = f32[64,32]{1,0} get-tuple-element(%wh), index=2
  ROOT %dot.2 = f32[128,32]{1,0} dot(%x2, %w2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_shape_parsing():
    assert shape_bytes("f32[128,64]{1,0}") == 128 * 64 * 4
    assert shape_bytes("bf16[2,3]") == 12
    assert shape_bytes("(f32[4], s32[2])") == 16 + 8
    assert shape_dims("bf16[2,3,4]{2,1,0}") == ("bf16", [2, 3, 4])
    assert shape_bytes("s32[]") == 4


def test_trip_count_multiplication():
    r = analyze(SYNTH_HLO)
    # dot.1 runs 7x inside the while; dot.2 once.  Each dot = 2*128*32*64.
    one_dot = 2 * 128 * 32 * 64
    assert r["flops"] == pytest.approx(one_dot * 8)
    # all-gather result 64*32*4 bytes, 7 iterations
    assert r["collective_bytes"]["all-gather"] == pytest.approx(
        64 * 32 * 4 * 7)
    assert r["collective_count"]["all-gather"] == 7


def test_fusion_bytes_counted_once():
    r = analyze(SYNTH_HLO)
    # fusion instruction bytes counted (result + operand), its BODY excluded
    fus_bytes = (128 * 64 * 4) * 2 * 7          # result + operand, 7 trips
    assert r["bytes"] >= fus_bytes


# ------------------------------------------------------------- sharding
def test_zero3_specs_divisible():
    from repro.dist import sharding as SH
    from repro.configs.base import get_config
    from repro.models.model import build_model
    mesh_shape = {"data": 16, "model": 16}

    class FakeMesh:
        shape = mesh_shape
    for name in ["yi-34b", "whisper-base", "qwen2-moe-a2.7b"]:
        cfg = get_config(name)
        model = build_model(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = SH.fsdp_param_specs(params, FakeMesh())

        def check(leaf, spec):
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                size = 1
                axes = ax if isinstance(ax, tuple) else (ax,)
                for a in axes:
                    size *= mesh_shape[a]
                assert leaf.shape[dim] % size == 0, (name, leaf.shape, spec)
        jax.tree.map(check, params, specs,
                     is_leaf=lambda x: isinstance(x, P))


def test_semantic_specs_have_branch_axis():
    from repro.dist import sharding as SH
    from repro.configs.base import get_config
    from repro.models.model import build_model

    class FakeMesh:
        shape = {"data": 16, "model": 16}
    cfg = get_config("stablelm-1.6b").semantic(16)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = SH.semantic_param_specs(params, FakeMesh())
    for spec in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        assert spec[0] == "model"  # branch dim always over 'model'


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and no other directory is set in code;
    without it the cache sits at the fixed in-checkout path."""
    from repro.launch import cache

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(cache.REPO_ROOT / ".jax_cache")
            assert cache.enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
            assert (cache.REPO_ROOT / "pyproject.toml").exists()
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                               str(tmp_path / env_dir))
            assert cache.enable_compile_cache() == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
