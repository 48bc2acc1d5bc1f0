"""Shared model-config + parameter plumbing for the assigned architectures.

Sharding convention (DESIGN.md §5), mesh axes (pod, data, model):
  * TP over ``model``: q-head dim of attention, d_ff of MLPs, experts of
    MoE, vocab of embedding/head.
  * ZeRO-3/FSDP over ``data``: the other matrix dim of every large weight.
  * ``pod`` is pure DP (params replicated across pods; XLA all-reduces
    grads over it automatically).

Head padding: jit refuses unevenly divisible shardings, so q/kv heads are
padded to the minimal (KVp, G') with KVp·G' % model == 0 that preserves the
original q→kv group mapping; padded slots are hard-masked to zero.  The
padding is *deliberately visible* in the roofline's MODEL_FLOPS/HLO_FLOPS
ratio.

Vocab is padded to a multiple of 256 (whisper's 51865); padded logits get
a -inf additive mask so the loss is exact.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

MODEL_AXIS_SIZE = 16  # production TP width; all padding is computed for it


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny"
    family: str = "dense"       # dense|moe|rwkv|hybrid|encdec|vlm
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 64
    d_ff: int = 1024
    vocab: int = 1024
    qk_norm: bool = False
    qkv_bias: bool = False
    norm: str = "rmsnorm"       # rmsnorm | layernorm
    rope_theta: float = 1e4
    norm_eps: float = 1e-6
    # moe
    n_experts: int = 0
    top_k: int = 0
    moe_interleave: int = 1     # 1 = every layer is MoE; 2 = every other
    capacity_factor: float = 1.25
    # hybrid (recurrentgemma): repeating block pattern
    pattern: tuple = ()         # e.g. ("rglru", "rglru", "attn")
    local_window: int = 0       # >0: sliding-window attention
    d_rnn: int = 0
    # enc-dec (whisper)
    n_enc_layers: int = 0
    # modality stub: fraction (numerator/denominator) of the sequence that
    # arrives as precomputed frontend embeddings
    frontend: str = "none"      # none | frames | patches
    frontend_len_div: int = 4   # frontend tokens = seq // this
    tie_embeddings: bool = False
    # execution
    q_chunk: int = 512
    kv_cache_dtype: str = "bf16"  # bf16 | int8 (per-token-per-head scales)
    remat: bool = True
    remat_policy: str = "none"  # none | weights (save FSDP-gathered weights
                                # so the bwd recompute doesn't re-gather)
    grad_dtype: str = "f32"     # f32 | bf16 gradient collectives
    scan_unroll: bool = False  # cost-probe: unroll layer scans so HLO cost_analysis counts every layer
    model_axis: int = MODEL_AXIS_SIZE
    optimizer: str = "adamw"    # adamw | adafactor
    learning_rate: float = 3e-4
    # ---- attention sharding mode ('heads' baseline; see EXPERIMENTS §Perf)
    attn_impl: str = "padded_heads"

    # ------------------------------------------------------------- padding
    @property
    def group_size(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def padded_heads(self) -> tuple[int, int]:
        """(KVp, Gp): minimal padded kv-head count and group size such that
        KVp*Gp is divisible by the model axis and the original q->kv group
        mapping embeds at (kv, g<G)."""
        kv, g = self.n_kv_heads, self.group_size
        best = None
        for kvp in range(kv, kv + self.model_axis + 1):
            for gp in range(g, g + self.model_axis + 1):
                hp = kvp * gp
                if hp % self.model_axis == 0:
                    if best is None or hp < best[0] * best[1]:
                        best = (kvp, gp)
        return best

    @property
    def n_heads_padded(self) -> int:
        kvp, gp = self.padded_heads
        return kvp * gp

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 256) * 256

    def head_mask(self) -> jax.Array:
        """(KVp, Gp) 1.0 for real heads, 0.0 for padding."""
        kvp, gp = self.padded_heads
        kv, g = self.n_kv_heads, self.group_size
        m = np.zeros((kvp, gp), np.float32)
        m[:kv, :g] = 1.0
        return jnp.asarray(m)

    def vocab_mask(self) -> jax.Array:
        """(Vp,) additive logits mask: 0 for real ids, -inf for padding."""
        m = np.zeros((self.padded_vocab,), np.float32)
        m[self.vocab :] = -1e9
        return jnp.asarray(m)


# --------------------------------------------------------------------------
# parameter containers: parallel (params, specs) pytrees
# --------------------------------------------------------------------------


class ParamFactory:
    """Builds (params, specs) trees together.  fp32 master weights; forward
    passes cast to bf16."""

    def __init__(self, key: jax.Array):
        self._key = key
        self.specs: dict[str, Any] = {}

    def key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def dense(self, shape, spec, scale=None):
        fan_in = shape[0] if len(shape) >= 2 else 1
        scale = scale if scale is not None else fan_in**-0.5
        return (
            jax.random.normal(self.key(), shape, jnp.float32) * scale,
            P(*spec),
        )

    def zeros(self, shape, spec):
        return jnp.zeros(shape, jnp.float32), P(*spec)

    def ones(self, shape, spec):
        return jnp.ones(shape, jnp.float32), P(*spec)


def split_tree(tree):
    """{(array, spec)} nested tree -> (params tree, specs tree)."""
    is_leaf = lambda x: isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], P)
    params = jax.tree.map(lambda x: x[0], tree, is_leaf=is_leaf)
    specs = jax.tree.map(lambda x: x[1], tree, is_leaf=is_leaf)
    return params, specs


def stack_layer_trees(trees):
    """Stack per-layer (params, specs) trees along a new leading dim."""
    params = jax.tree.map(lambda *xs: jnp.stack(xs), *[t[0] for t in trees])
    spec0 = trees[0][1]
    specs = jax.tree.map(lambda s: P(None, *s), spec0)
    return params, specs


def cast_bf16(tree):
    return jax.tree.map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x, tree
    )


def dp_spec(mesh_axis_names) -> tuple:
    """The batch-sharding axes: ('pod','data') on a multi-pod mesh."""
    return ("pod", "data") if "pod" in mesh_axis_names else ("data",)


def constrain(x, spec: P):
    """with_sharding_constraint that no-ops outside a mesh context (single-
    device smoke tests) and inside shard_map bodies (Manual axes), so the
    same model code runs everywhere."""
    m = jax.sharding.get_abstract_mesh()
    if m.empty or jax.sharding.AxisType.Manual in m.axis_types:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def wcast(w, dtype, gspec: P | None = None):
    """Cast a (FSDP-sharded fp32 master) weight for compute.

    gspec, when given, is the weight's *gathered* sharding (storage spec
    with the FSDP 'data' axis dropped, TP axis kept).  Constraining to it
    makes the all-gather happen at this tag — the same place GSPMD inserts
    it anyway — so remat_policy='weights' can SAVE the gathered value and
    the backward recompute stops re-gathering every weight
    (EXPERIMENTS.md §Perf maverick#2)."""
    out = w.astype(dtype)
    if gspec is not None:
        out = constrain(out, gspec)
    return jax.ad_checkpoint.checkpoint_name(out, "gathered_weights")


def make_remat(cfg, fn):
    """jax.checkpoint with the configured policy."""
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "weights":
        policy = jax.checkpoint_policies.save_only_these_names("gathered_weights")
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)
