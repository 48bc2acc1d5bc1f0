"""Launch-layer tests that don't need 512 devices: input specs, skip rules,
collective parsing, probe algebra, and a tiny-mesh lower+compile."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.dryrun import _combine, parse_collectives
from repro.launch.mesh import make_mesh
from repro.launch.input_specs import SHAPES, batch_specs, skip_reason


def test_skip_rules():
    from repro.configs import get_config

    assert skip_reason(get_config("qwen3-4b"), "long_500k") is not None
    assert skip_reason(get_config("rwkv6-1.6b"), "long_500k") is None
    assert skip_reason(get_config("recurrentgemma-9b"), "long_500k") is None
    assert skip_reason(get_config("qwen3-4b"), "train_4k") is None


def test_cell_count_is_40():
    from repro.configs import list_archs

    cells = [(a, s) for a in list_archs() for s in SHAPES]
    assert len(cells) == 40
    skipped = [
        (a, s) for a, s in cells
        if skip_reason(__import__("repro.configs", fromlist=["get_config"]).get_config(a), s)
    ]
    assert len(skipped) == 8  # the eight full-attention long_500k cells


def test_probe_combine_algebra():
    # base=5, gamma=0.25, body=3, L=8, trips=8 -> corrected = 5 + 2 + 24
    m0 = 5.0
    mH = 5.0 + 0.25 * 4 + 3.0
    mL = 5.0 + 0.25 * 8 + 3.0
    assert abs(_combine(mL, mH, m0, 8, 4, 8) - 31.0) < 1e-9


def test_parse_collectives():
    hlo = """
ENTRY %main {
  %ag = bf16[2,1024]{1,0} all-gather(bf16[1,1024]{1,0} %x), replica_groups={}
  %ar = f32[256]{0} all-reduce(f32[256]{0} %y), to_apply=%add
  %a2a = (f32[8,4]{1,0}, f32[8,4]{1,0}) all-to-all(f32[8,4] %a, f32[8,4] %b)
  %cp = u8[128]{0} collective-permute(u8[128]{0} %z)
  %notacoll = f32[2]{0} add(f32[2] %p, f32[2] %q)
}
"""
    got = parse_collectives(hlo)
    assert got["all-gather"] == 2 * 1024 * 2
    assert got["all-reduce"] == 256 * 4
    assert got["all-to-all"] == 2 * 8 * 4 * 4
    assert got["collective-permute"] == 128
    assert got["total"] == sum(got[k] for k in
                               ("all-gather", "all-reduce", "reduce-scatter",
                                "all-to-all", "collective-permute"))


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 host devices")
def test_tiny_mesh_lower_compile_train():
    """The dry-run path end to end on a 2x2 mesh with a reduced config —
    same code path as the 512-device run, in milliseconds."""
    from repro.configs import get_reduced
    from repro.models.registry import get_model
    from repro.train.loop import make_train_step
    from repro.train.optimizer import get_optimizer

    cfg = get_reduced("qwen3-4b")
    mesh = make_mesh((2, 2), ("data", "model"))
    model = get_model(cfg)
    pshapes, pspecs = model.abstract_init()
    opt = get_optimizer(cfg.optimizer, cfg.learning_rate)
    oshapes = jax.eval_shape(opt.init, pshapes)
    ospecs = opt.state_specs(pspecs, pshapes)
    nsh = lambda spec: jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec, is_leaf=lambda x: isinstance(x, P)
    )
    B, S = 4, 32
    batch = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }
    bspecs = {"tokens": P(("data",), None), "labels": P(("data",), None)}
    fn = make_train_step(model, opt, ("data",))
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            fn,
            in_shardings=(nsh(pspecs), nsh(ospecs), NamedSharding(mesh, P()), nsh(bspecs)),
        ).lower(pshapes, oshapes, jax.ShapeDtypeStruct((), jnp.int32), batch)
        compiled = lowered.compile()
    assert compiled.cost_analysis() is not None


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 host devices")
def test_tiny_mesh_lower_compile_decode():
    from repro.configs import get_reduced
    from repro.launch.input_specs import decode_specs
    from repro.models.registry import get_model

    cfg = get_reduced("qwen3-4b")
    mesh = make_mesh((2, 2), ("data", "model"))
    model = get_model(cfg)
    pshapes, pspecs = model.abstract_init()
    cshapes, cspecs = model.abstract_cache(4, 64)
    nsh = lambda spec: jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec, is_leaf=lambda x: isinstance(x, P)
    )
    with jax.set_mesh(mesh):
        fn = lambda params, cache, token, p: model.decode_step(
            mesh, params, cache, token, p, ("data",)
        )
        lowered = jax.jit(
            fn,
            in_shardings=(
                nsh(pspecs), nsh(cspecs),
                NamedSharding(mesh, P(("data",))), NamedSharding(mesh, P()),
            ),
        ).lower(
            pshapes, cshapes,
            jax.ShapeDtypeStruct((4,), jnp.int32), jax.ShapeDtypeStruct((), jnp.int32),
        )
        compiled = lowered.compile()
    assert compiled is not None


def test_compile_cache_dir_from_env(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself: the helper
    returns it and sets no other directory."""
    from repro.launch.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_default_is_fixed_and_ignored(monkeypatch):
    """Unset, the cache goes to one fixed directory inside the checkout,
    which git ignores."""
    import pathlib

    from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache

    repo = pathlib.Path(__file__).resolve().parents[1]
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (repo / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
