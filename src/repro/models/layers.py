"""Neural building blocks: norms, RoPE, GQA attention (chunked-causal train,
flash-decode for serving), SwiGLU MLP, capacity-based MoE dispatch.

All functions are pure; shapes use B=batch, S=seq, K=kv heads (padded),
G=group size (padded), D=d_model, F=d_ff, E=experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.base import constrain, wcast


def rmsnorm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def layernorm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * scale.astype(x.dtype) + bias.astype(x.dtype)


def rope(x, positions, theta=1e4):
    """x: (..., S, heads..., dh); positions: (..., S) int32."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    # broadcast over head dims between S and dh
    extra = x.ndim - ang.ndim - 1
    for _ in range(extra):
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention — training / prefill (full sequence, q-chunked)
# --------------------------------------------------------------------------


def attention_full(
    q, k, v, head_mask, *, group_size, causal=True, window=0, q_chunk=512
):
    """GQA attention over a full sequence.

    q: (B, S, H, dh) with H = KVp * Gp sharded over `model`; k, v:
    (B, T, KVp, dh) replicated over `model` (kv weights are small; this
    keeps attention collective-free).  head_mask: (H,) zeros padded heads.
    KV heads are expanded locally (`repeat`); XLA fuses the repeat with the
    per-chip head slice.  Queries are processed in chunks via lax.scan so
    the live score tensor is (B, c, H, T) and the HLO is O(1) in S.
    """
    B, S, H, dh = q.shape
    T = k.shape[1]
    c = min(q_chunk, S)
    s_pad = -S % c
    if s_pad:  # ragged tail: pad queries, slice the outputs back off
        q = jnp.pad(q, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
    Sp = S + s_pad
    scale = dh**-0.5

    k = jnp.repeat(k, group_size, axis=2)  # (B, T, H, dh)
    v = jnp.repeat(v, group_size, axis=2)
    qc = q.reshape(B, Sp // c, c, H, dh).swapaxes(0, 1)  # (nc, B, c, H, dh)

    def chunk(carry, inp):
        ci, qb = inp
        qpos = ci * c + jnp.arange(c)
        kpos = jnp.arange(T)
        s = jnp.einsum(
            "bchd,bthd->bhct", qb.astype(jnp.float32) * scale, k.astype(jnp.float32)
        )
        mask = jnp.ones((c, T), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window > 0:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(mask[None, None, :, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhct,bthd->bchd", p, v.astype(jnp.float32))
        o = o * head_mask[None, None, :, None]
        return carry, o.astype(q.dtype)

    _, out = jax.lax.scan(chunk, None, (jnp.arange(Sp // c), qc))
    return out.swapaxes(0, 1).reshape(B, Sp, H, dh)[:, :S]


# --------------------------------------------------------------------------
# attention — decode (flash-decode: cache sequence-sharded over `model`)
# --------------------------------------------------------------------------


def quantize_kv(x, axis=-1):
    """int8-quantize along `axis` with one fp32 scale per slice (the ToaD
    move — shared compact value representation — applied to the decode
    cache: halves the HBM-resident bytes vs bf16)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def flash_decode(
    mesh, dp, q, k_cache, v_cache, k_new, v_new, pos, head_mask, group_size,
    write=True, k_scale=None, v_scale=None,
):
    """One decode step against a sequence-sharded KV cache (flash-decoding).

    q: (B, H, dh) replicated over `model`; k_cache/v_cache: (B, Smax, KVp, dh)
    sharded over `model` along Smax; k_new/v_new: (B, KVp, dh); pos: ()
    write index.  The new token is written by the chip owning its slot;
    each chip computes a partial softmax over its chunk and results combine
    with the log-sum-exp trick (one small psum).  Per-chip memory is
    O(Smax/model) — this is what makes 32k/500k-context decode fit.

    When k_scale/v_scale (B, Smax, KVp) are given, the caches are int8 with
    per-(token, head) scales; the new token is quantized before its write.

    Returns (attn out (B, H, dh), updated caches [+ updated scales]).
    """
    dh = q.shape[-1]
    scale = dh**-0.5
    int8 = k_scale is not None

    def local(q, kc, vc, kn, vn, pos, ks=None, vs=None):
        s_loc = kc.shape[1]
        ax = jax.lax.axis_index("model")
        if int8:
            kn, kn_s = quantize_kv(kn)
            vn, vn_s = quantize_kv(vn)
        if write:
            off = pos - ax * s_loc
            owned = (off >= 0) & (off < s_loc)
            safe = jnp.clip(off, 0, s_loc - 1)
            upd = lambda c, n: jnp.where(
                owned, jax.lax.dynamic_update_slice_in_dim(c, n[:, None], safe, 1), c
            )
            kc = upd(kc, kn)
            vc = upd(vc, vn)
            if int8:
                ks = upd(ks, kn_s)
                vs = upd(vs, vn_s)

        if int8:
            kd = kc.astype(jnp.float32) * ks[..., None]
            vd = vc.astype(jnp.float32) * vs[..., None]
        else:
            kd, vd = kc, vc
        ke = jnp.repeat(kd, group_size, axis=2)  # (B, s_loc, H, dh)
        ve = jnp.repeat(vd, group_size, axis=2)
        kpos = ax * s_loc + jnp.arange(s_loc)
        s = jnp.einsum(
            "bhd,bthd->bht", q.astype(jnp.float32) * scale, ke.astype(jnp.float32)
        )
        s = jnp.where((kpos <= pos)[None, None, :], s, -1e30)
        m = jnp.max(s, axis=-1)                                   # (B, H)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bht,bthd->bhd", p, ve.astype(jnp.float32))
        mg = jax.lax.pmax(m, "model")
        alpha = jnp.exp(m - mg)
        num = jax.lax.psum(o * alpha[..., None], "model")
        den = jax.lax.psum(l * alpha, "model")
        out = (num / jnp.maximum(den, 1e-30)[..., None]).astype(q.dtype)
        out = out * head_mask[None, :, None].astype(q.dtype)
        if int8:
            return out, kc, vc, ks, vs
        return out, kc, vc

    cache_spec = P(dp, "model", None, None)
    scale_spec = P(dp, "model", None)
    in_specs = [P(dp, None, None), cache_spec, cache_spec,
                P(dp, None, None), P(dp, None, None), P()]
    out_specs = [P(dp, None, None), cache_spec, cache_spec]
    args = [q, k_cache, v_cache, k_new, v_new, pos]
    if int8:
        in_specs += [scale_spec, scale_spec]
        out_specs += [scale_spec, scale_spec]
        args += [k_scale, v_scale]
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=tuple(in_specs), out_specs=tuple(out_specs),
        check_vma=False,
    )(*args)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------


def swiglu(x, wi, wg, wo, constrain=None):
    """SwiGLU MLP; wi/wg column-parallel, wo row-parallel (one psum)."""
    h = jnp.einsum("bsd,df->bsf", x, wcast(wi, x.dtype, P(None, "model")))
    g = jnp.einsum("bsd,df->bsf", x, wcast(wg, x.dtype, P(None, "model")))
    h = jax.nn.silu(g) * h
    if constrain is not None:
        h = constrain(h)
    return jnp.einsum("bsf,fd->bsd", h, wcast(wo, x.dtype, P("model", None)))


def gelu_mlp(x, wi, bi, wo, bo):
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, wi.astype(x.dtype)) + bi.astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", h, wcast(wo, x.dtype, P("model", None))) + bo.astype(x.dtype)


# --------------------------------------------------------------------------
# MoE (capacity-factor scatter dispatch; experts sharded over `model`)
# --------------------------------------------------------------------------


def _moe_local(x, w_router, w_in, w_gate, w_out, *, top_k, capacity_factor,
               n_experts, e_loc_offset=None):
    """Single-device MoE math over LOCAL tokens and LOCAL experts.

    x: (B_loc, S, D); w_in/w_gate: (E_loc, D, F); w_out: (E_loc, F, D);
    w_router: (D, E) full.  Routing runs over the full expert space
    (replicated across model ranks — deterministic), each rank materializes
    buffers only for its own experts and returns a PARTIAL output (tokens
    routed elsewhere contribute zero); the caller psums over `model`.
    """
    B, S, D = x.shape
    E = n_experts
    E_loc = w_in.shape[0]
    N = B * S
    xt = x.reshape(N, D)
    logits = jnp.einsum("nd,de->ne", xt, w_router.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)                    # (N, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # keep only slots routed to this rank's experts; the rest land in a
    # trash bucket E_loc
    off = 0 if e_loc_offset is None else e_loc_offset
    rel = top_e - off
    mine = (rel >= 0) & (rel < E_loc)
    flat_e = jnp.where(mine, rel, E_loc).reshape(-1)              # (N*k,)

    # per-expert rank via stable sort (a cumsum-of-one-hot rank is modeled
    # by XLA as an O(N^2) reduce-window; see EXPERIMENTS.md §Perf)
    cap = int(max(1, capacity_factor * top_k * N / E))
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E_loc + 1, dtype=flat_e.dtype))
    rank_sorted = jnp.arange(flat_e.shape[0], dtype=jnp.int32) - starts[sorted_e]
    rank = jnp.zeros_like(flat_e).at[order].set(rank_sorted)
    keep = (rank < cap) & mine.reshape(-1)
    safe_rank = jnp.minimum(rank, cap - 1)
    safe_e = jnp.minimum(flat_e, E_loc - 1)

    xk = jnp.repeat(xt, top_k, axis=0)                            # (N*k, D)
    buf = jnp.zeros((E_loc, cap, D), x.dtype)
    buf = buf.at[safe_e, safe_rank].add(
        jnp.where(keep[:, None], xk, 0.0).astype(x.dtype)
    )

    h = jnp.einsum("ecd,edf->ecf", buf, wcast(w_in, x.dtype, P("model", None, None)))
    g = jnp.einsum("ecd,edf->ecf", buf, wcast(w_gate, x.dtype, P("model", None, None)))
    h = jax.nn.silu(g) * h
    y = jnp.einsum("ecf,efd->ecd", h, wcast(w_out, x.dtype, P("model", None, None)))      # (E_loc, cap, D)

    gathered = y[safe_e, safe_rank]                               # (N*k, D)
    w = jnp.where(keep, top_p.reshape(-1), 0.0).astype(x.dtype)
    out = (gathered * w[:, None]).reshape(N, top_k, D).sum(axis=1)
    return out.reshape(B, S, D)


def moe_block(x, w_router, w_in, w_gate, w_out, *, top_k, capacity_factor):
    """Expert-parallel MoE: local dispatch + partial-output psum.

    Tokens never leave their data shard; each `model` rank routes the
    (model-replicated) local tokens to its own E/model experts and psums
    the partial outputs — one (B_loc, S, D) all-reduce per layer, the same
    collective Megatron's row-parallel MLP pays, instead of global-sort /
    all-to-all dispatch (see EXPERIMENTS.md §Perf for the measured path
    here: unconstrained GSPMD 256x flops -> global sort 608 GB/dev
    collectives -> this).
    """
    E = w_in.shape[0]
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or "model" not in mesh.axis_names:
        return _moe_local(
            x, w_router, w_in, w_gate, w_out,
            top_k=top_k, capacity_factor=capacity_factor, n_experts=E,
        )

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def local(x, w_router, w_in, w_gate, w_out):
        e_loc = w_in.shape[0]
        off = jax.lax.axis_index("model") * e_loc
        out = _moe_local(
            x, w_router, w_in, w_gate, w_out,
            top_k=top_k, capacity_factor=capacity_factor, n_experts=E,
            e_loc_offset=off,
        )
        return jax.lax.psum(out, "model")

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(dp, None, None),
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=P(dp, None, None),
        check_vma=False,
    )(x, w_router, w_in, w_gate, w_out)
