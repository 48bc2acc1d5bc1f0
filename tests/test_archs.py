"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes + no NaNs; plus decode-vs-prefill parity for
one arch per family."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_reduced
from repro.models.registry import get_model

B, S = 2, 32


def _batch(cfg, with_labels=True):
    batch = {}
    if cfg.family == "encdec":
        batch["frames"] = jnp.ones((B, S // cfg.frontend_len_div, cfg.d_model), jnp.bfloat16)
        batch["tokens"] = jnp.zeros((B, S), jnp.int32) + 3
    elif cfg.family == "vlm":
        pe = S // cfg.frontend_len_div
        batch["embeds"] = jnp.ones((B, pe, cfg.d_model), jnp.bfloat16)
        batch["tokens"] = jnp.zeros((B, S - pe), jnp.int32) + 3
    else:
        batch["tokens"] = jnp.zeros((B, S), jnp.int32) + 3
    if with_labels:
        batch["labels"] = jnp.ones((B, S), jnp.int32)
    return batch


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_smoke_train_step(name, mesh11):
    cfg = get_reduced(name)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    with jax.set_mesh(mesh11):
        loss, grads = jax.jit(
            lambda p, b: jax.value_and_grad(lambda q: model.train_loss(q, b))(p)
        )(params, batch)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_smoke_prefill_decode(name, mesh11):
    cfg = get_reduced(name)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, with_labels=False)
    with jax.set_mesh(mesh11):
        logits, cache = jax.jit(lambda p, b: model.prefill(p, b))(params, batch)
        assert logits.shape == (B, cfg.padded_vocab)
        assert bool(jnp.all(jnp.isfinite(logits[:, : cfg.vocab])))

        def grow(x):
            if hasattr(x, "ndim") and x.ndim == 5 and x.shape[2] in (S, S // cfg.frontend_len_div):
                if x.shape[2] == S:
                    return jnp.pad(x, ((0, 0), (0, 0), (0, 8), (0, 0), (0, 0)))
            return x

        cache = jax.tree.map(grow, cache)
        tok = jnp.argmax(logits[:, : cfg.vocab], -1).astype(jnp.int32)
        ld, cache2 = jax.jit(
            lambda p, c, t, pos: model.decode_step(mesh11, p, c, t, pos)
        )(params, cache, tok, jnp.asarray(S, jnp.int32))
        assert ld.shape == (B, cfg.padded_vocab)
        assert bool(jnp.all(jnp.isfinite(ld[:, : cfg.vocab])))


@pytest.mark.parametrize("name", ["qwen3-4b", "rwkv6-1.6b", "recurrentgemma-9b"])
def test_decode_matches_prefill(name, mesh11):
    """Autoregressive consistency: decode at position S equals a fresh
    prefill over S+1 tokens (bf16 tolerance)."""
    cfg = get_reduced(name)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
    with jax.set_mesh(mesh11):
        logits, cache = jax.jit(lambda p, b: model.prefill(p, b))(params, {"tokens": toks})

        def grow(x):
            if hasattr(x, "ndim") and x.ndim == 5 and x.shape[2] == S:
                return jnp.pad(x, ((0, 0), (0, 0), (0, 8), (0, 0), (0, 0)))
            return x

        cache = jax.tree.map(grow, cache)
        tok = jnp.argmax(logits[:, : cfg.vocab], -1).astype(jnp.int32)
        ld, _ = jax.jit(
            lambda p, c, t, pos: model.decode_step(mesh11, p, c, t, pos)
        )(params, cache, tok, jnp.asarray(S, jnp.int32))
        toks2 = jnp.concatenate([toks, tok[:, None]], axis=1)
        lp2, _ = jax.jit(lambda p, b: model.prefill(p, b))(params, {"tokens": toks2})
    a = np.asarray(ld[:, : cfg.vocab], np.float32)
    b = np.asarray(lp2[:, : cfg.vocab], np.float32)
    # bf16 activations: compare argmax + loose numeric tolerance
    assert np.mean(np.argmax(a, -1) == np.argmax(b, -1)) >= 0.95
    np.testing.assert_allclose(a, b, atol=0.15, rtol=0.1)


def test_head_padding_configs():
    """Every production config's padded head layout divides the TP axis and
    preserves the real q->kv mapping."""
    from repro.configs import get_config

    for name in ARCHS:
        cfg = get_config(name)
        kvp, gp = cfg.padded_heads
        assert (kvp * gp) % cfg.model_axis == 0
        assert kvp >= cfg.n_kv_heads
        assert gp >= cfg.group_size
        mask = np.asarray(cfg.head_mask())
        assert mask.sum() == cfg.n_kv_heads * cfg.group_size == cfg.n_heads


def test_param_counts_match_billing():
    """Total parameter counts are in the advertised ballpark."""
    from repro.configs import get_config
    from repro.launch.dryrun import count_active_params, count_params
    from repro.models.registry import get_model

    expected = {
        "qwen3-4b": (3e9, 6e9),
        "llama3.2-3b": (2.5e9, 5e9),
        "qwen1.5-32b": (28e9, 40e9),
        "stablelm-12b": (9e9, 15e9),
        "olmoe-1b-7b": (5e9, 9e9),
        "llama4-maverick-400b-a17b": (350e9, 450e9),
        "rwkv6-1.6b": (1.2e9, 2.5e9),
        "whisper-small": (0.15e9, 0.4e9),
        "recurrentgemma-9b": (7e9, 12e9),
        "llava-next-34b": (30e9, 42e9),
    }
    for name, (lo, hi) in expected.items():
        cfg = get_config(name)
        shapes, _ = get_model(cfg).abstract_init()
        n = count_params(shapes)
        assert lo < n < hi, f"{name}: {n/1e9:.2f}B params out of range [{lo/1e9},{hi/1e9}]"
        if cfg.n_experts:
            na = count_active_params(cfg, shapes)
            assert na < n / 4, f"{name}: active {na/1e9:.1f}B not sparse"


def test_int8_kv_cache_parity(mesh11):
    """int8 decode cache (per-token-per-head scales) preserves decode
    behaviour: identical argmax, ~1% relative logit error."""
    import dataclasses

    outs = {}
    for dt in ("bf16", "int8"):
        cfg = dataclasses.replace(get_reduced("qwen3-4b"), kv_cache_dtype=dt)
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(1))
        toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
        with jax.set_mesh(mesh11):
            logits, cache = jax.jit(lambda p, b: model.prefill(p, b))(
                params, {"tokens": toks}
            )

            def grow(x):
                if hasattr(x, "ndim") and x.ndim >= 4 and x.shape[2] == S:
                    pad = [(0, 0)] * x.ndim
                    pad[2] = (0, 8)
                    return jnp.pad(x, pad)
                return x

            cache = jax.tree.map(grow, cache)
            tok = jnp.argmax(logits[:, : cfg.vocab], -1).astype(jnp.int32)
            ld, _ = jax.jit(
                lambda p, c, t, pos: model.decode_step(mesh11, p, c, t, pos)
            )(params, cache, tok, jnp.asarray(S, jnp.int32))
        outs[dt] = np.asarray(ld[:, : cfg.vocab], np.float32)
    agree = (outs["bf16"].argmax(-1) == outs["int8"].argmax(-1)).mean()
    rel = np.abs(outs["bf16"] - outs["int8"]).max() / np.abs(outs["bf16"]).max()
    assert agree == 1.0
    assert rel < 0.05
