"""Sharding rules, spec sanitisation, and pipeline parallelism."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as PS

from repro.configs.base import get_config
from repro.distributed.sharding import (
    DECODE_RULES, LONG_CONTEXT_RULES, REGISTRATION_RULES, TRAIN_RULES,
    abstract_mesh, dedup_specs, partition_specs, sanitize_specs,
)
from repro.models import model as M
from repro.models.schema import abstract_params


def _mesh22():
    return jax.make_mesh((1, 1), ("data", "model"))


def test_rules_cover_all_logical_axes():
    r = TRAIN_RULES(("data", "model"))
    for ax in ("batch", "embed", "heads", "ff", "vocab", "experts", "seq"):
        assert ax in r
    r2 = TRAIN_RULES(("pod", "data", "model"))
    assert r2["batch"] == ("pod", "data")
    assert DECODE_RULES(("data", "model"))["kv_len"] == "model"
    assert LONG_CONTEXT_RULES(("data", "model"))["batch"] is None
    # registration serving: batch over data, all per-pair axes replicated
    rr = REGISTRATION_RULES(("data",))
    assert rr["batch"] == ("data",)
    assert rr.spec(("batch", "vol_x", "vol_y", "vol_z")) == \
        PS(("data",), None, None, None)
    assert REGISTRATION_RULES(("pod", "data"))["batch"] == ("pod", "data")


def test_sanitize_drops_nondivisible_and_duplicates():
    mesh = abstract_mesh((2, 2), ("data", "model"))
    leaf = jax.ShapeDtypeStruct((6, 3), jnp.float32)  # 6 % 2 == 0, 3 % 2 != 0
    spec = PS("data", "model")
    out = sanitize_specs(leaf, spec, mesh)
    assert out == PS("data", None)
    # duplicate axis across dims: second occurrence dropped
    leaf2 = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    out2 = sanitize_specs(leaf2, PS("data", "data"), mesh)
    assert out2 == PS("data", None)


def test_dedup_specs():
    out = dedup_specs(PS(None, "data", "data", "model"))
    assert out == PS(None, "data", None, "model")


@pytest.mark.parametrize("arch", ["qwen1.5-32b", "arctic-480b", "xlstm-1.3b",
                                  "llama-3.2-vision-90b"])
def test_param_specs_structurally_match(arch):
    """Every parameter leaf gets a spec of matching rank."""
    cfg = get_config(arch)
    schema = M.model_schema(cfg)
    specs = partition_specs(schema, TRAIN_RULES(("data", "model")))
    ab = abstract_params(schema)
    flat_a = jax.tree_util.tree_leaves(ab)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, PS))
    assert len(flat_a) == len(flat_s)
    for a, s in zip(flat_a, flat_s):
        assert len(s) == len(a.shape), (a.shape, s)


def test_head_dims_flat_divisible_by_16():
    """The flattened H*hd layout is 16-divisible for every assigned arch
    (the reason attention params store heads fused — DESIGN.md §5)."""
    for arch in ["qwen1.5-32b", "gemma3-1b", "gemma2-2b", "internlm2-1.8b",
                 "qwen2-moe-a2.7b", "arctic-480b", "hymba-1.5b",
                 "whisper-base", "llama-3.2-vision-90b"]:
        cfg = get_config(arch)
        hd = cfg.resolved_head_dim
        assert (cfg.num_heads * hd) % 16 == 0, arch
        assert (cfg.num_kv_heads * hd) % 16 == 0, arch


def test_pipeline_parallel_matches_serial():
    """GPipe stage runner == serial layer stack (1-stage degenerate + math
    identity on a single-device 'pp' axis)."""
    from repro.distributed.pipeline import pipeline_apply

    mesh = jax.make_mesh((1,), ("pp",))
    rng = np.random.default_rng(0)
    n_stages, d = 1, 8
    w = jnp.asarray(rng.standard_normal((n_stages, d, d)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, d)), jnp.float32)

    def stage(params, h):
        return jnp.tanh(h @ params)

    out = pipeline_apply(stage, w, x, mesh=mesh, axis="pp", n_micro=2)
    ref = x
    for i in range(n_stages):
        ref = jnp.tanh(ref @ w[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_cache_specs_match_cache_structure():
    for arch in ["gemma2-2b", "xlstm-1.3b", "whisper-base",
                 "llama-3.2-vision-90b", "hymba-1.5b"]:
        cfg = get_config(arch, smoke=True)
        cache = M.abstract_cache(cfg, 2, 16)
        specs = M.cache_partition_specs(cfg, DECODE_RULES(("data", "model")))
        flat_c = jax.tree_util.tree_flatten_with_path(cache)[0]
        flat_s = {jax.tree_util.keystr(p): s for p, s in
                  jax.tree_util.tree_flatten_with_path(
                      specs, is_leaf=lambda x: isinstance(x, PS))[0]}
        for path, leaf in flat_c:
            key = jax.tree_util.keystr(path)
            assert key in flat_s, key
            assert len(flat_s[key]) <= len(leaf.shape), (key, leaf.shape)


def test_pipeline_parallel_multistage_subprocess():
    """4-stage pipeline vs serial — needs 4 devices, so runs in a fresh
    process with forced host devices (same trick as the dry-run)."""
    import subprocess, sys, os, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_apply
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pp",))
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.standard_normal((4, 8, 8)) * 0.3, jnp.float32)
        x = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
        out = pipeline_apply(lambda p, h: jnp.tanh(h @ p), w, x,
                             mesh=mesh, axis="pp", n_micro=4)
        ref = x
        for i in range(4):
            ref = jnp.tanh(ref @ w[i])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        print("PIPELINE_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
    assert "PIPELINE_OK" in r.stdout, r.stderr[-2000:]
