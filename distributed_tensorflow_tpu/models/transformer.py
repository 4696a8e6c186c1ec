"""Decoder-only transformer LM — the framework's long-context model family.

The reference has no sequence models (SURVEY §5.7); this model exists so the
framework's first-class long-context machinery (``ops.attention`` blockwise/
flash kernels, ``parallel.ring_attention`` sequence parallelism) has a
production consumer, the same way ``MnistCNN`` consumes the data-parallel
stack.

TPU-first choices:
  * bf16 compute / f32 params (MXU-native), static shapes throughout
  * pre-norm blocks, GELU MLP, learned positional embeddings taken by
    **global** position so a sequence shard on device i embeds positions
    [i·S_loc, (i+1)·S_loc) — the hook sequence parallelism needs
  * attention implementation is injectable: 'dense' (short seq), 'blockwise'
    (long seq, differentiable scan), 'flash' (Pallas kernel), or a callable
    (ring attention closure from the parallel layer)
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu.models.mamba import mamba_mixer
from distributed_tensorflow_tpu.models.moe import routed_experts
from distributed_tensorflow_tpu.ops import attention as A
from distributed_tensorflow_tpu.ops.grouped_matmul import grouped_matmul_fits
from distributed_tensorflow_tpu.ops.rope import apply_rope as _rotate_heads
from distributed_tensorflow_tpu.ops.rope import (
    rope_tables,
    yarn_inv_freq,
    yarn_mscale,
    yarn_tables,
)


def default_compute_dtype():
    """bf16 on TPU, f32 elsewhere — the ONE place the CLIs (train_lm,
    serve_lm ``--demo``) and the bundle loader pick it, so a model is
    trained, loaded and served in the same dtype on the same machine."""
    return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32


def apply_rope(x, cos, sin):
    """``ops.rope.apply_rope`` on the leading ``2 * cos.shape[-1]``
    dimensions of each head (all of them unless ``rope_fraction`` < 1); the
    rest pass unrotated."""
    rot = 2 * cos.shape[-1]
    if rot == x.shape[-1]:
        return _rotate_heads(x, cos, sin)
    return jnp.concatenate(
        [_rotate_heads(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)


class EvaUnsupported(ValueError):
    """A feature that is not extended to an EVA config (``eva_window`` set):
    raised where the feature is asked for, never a silent fallback."""


class SlotStateUnsupported(ValueError):
    """A feature that is not extended to a config whose slots carry state
    beside K/V rows (CCA's convolution latents, ``cca_time0`` set; a Mamba-2
    layer's recurrent state, an ``M`` in ``layer_pattern``), to routed
    experts (``num_experts`` set) or to rows that are not K and V (latent
    attention's, ``kv_lora_rank`` set): raised where the feature is asked
    for, never a silent fallback."""


# The name the exception had while CCA's was the only slot state.
CcaUnsupported = SlotStateUnsupported


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 128
    num_heads: int = 4
    num_layers: int = 2
    d_ff: int = 512
    max_seq_len: int = 2048
    dropout_rate: float = 0.0
    attention: str | Callable = "dense"  # 'dense' | 'blockwise' | 'flash' | callable
    compute_dtype: Any = jnp.bfloat16
    # Dense-layer biases (qkv/proj/mlp/lm_head; LayerNorm keeps its affine
    # params either way). Default True for continuity with earlier rounds;
    # the bench flagship runs False — the modern-LM convention, worth a
    # measured ~2% of the flagship step: XLA emits each bias GRADIENT as a
    # separate whole-activation reduce pass it will not fuse into the
    # weight-grad matmul (9.8 ms/step at the flagship shape, XPlane r4).
    use_bias: bool = True
    # Grouped-query attention (None = multi-head, the default): K/V get
    # ``num_kv_heads`` heads and each group of num_heads/num_kv_heads query
    # heads shares one. The modern-LM KV design and a direct TPU lever:
    # the KV cache shrinks by the group factor (decode is KV-bandwidth
    # bound past small batches — BASELINE.md decode roofline) and the kv
    # projection matmuls shrink with it. num_heads must be divisible by
    # num_kv_heads. Supported everywhere: plain/MoE/pipeline model paths,
    # cached decode, and TpBlock (kv heads shard WITH their query groups —
    # needs num_kv_heads % tp == 0).
    num_kv_heads: int | None = None
    # Sliding-window attention (None = full causal): each token attends the
    # previous ``attention_window`` positions only (self included — the
    # Mistral convention). On TPU the flash kernels turn this into
    # O(S·window) compute AND kv DMA via two-sided block skipping/clamping;
    # the decode path masks the cache the same way.
    attention_window: int | None = None
    # Position encoding: 'learned' (additive max_seq_len x d_model table,
    # the historical default) or 'rope' (rotary embeddings, ops/rope.py):
    # q/k head vectors rotate by position-dependent angles BEFORE the
    # attention kernels — no position table params, relative offsets in the
    # dot product, fused-elementwise cost on TPU. Completes the
    # GQA + sliding-window + RoPE modern-attention trio.
    # 'none': no position signal at all (a hybrid whose recurrent layers
    # carry the order).
    position: str = "learned"  # 'learned' | 'rope' | 'none'
    rope_theta: float = 10000.0
    # KV-cache storage dtype for decode (None = compute_dtype): 'int8'
    # stores per-row-quantized keys/values (symmetric absmax over head_dim,
    # f32 scales laid out (B, KV, S) — S minor, so no lane-padding tax).
    # Decode is KV-cache-bandwidth bound past small batches (BASELINE.md
    # decode roofline: tokens/s scales with cache bytes read), so halving
    # cache bytes vs bf16 is a direct throughput lever that COMPOSES with
    # GQA's group factor. Dequantization happens in-register (the scale
    # factors out of the dot product over head_dim — applied to the score/
    # weight matrices, never re-materializing a dequantized cache).
    kv_cache_dtype: str | None = None  # None | 'int8'
    # Rematerialise each block on the backward pass (jax.checkpoint): saves
    # only block boundaries instead of every intermediate — activation memory
    # drops from O(L·S·(d_ff+4·d_model)) to O(L·S·d_model) + one block's
    # intermediates, for one extra forward's FLOPs. The standard long-context
    # trade on TPU, where HBM (not MXU) is the bottleneck.
    remat: bool = False
    # Weight-only quantization for serving (None = plain Dense): the four
    # per-block matmul projections (qkv/proj/mlp_in/mlp_out) become
    # ``models.quant.QuantDense`` — int8 per-output-channel (scale factors
    # out of the contraction exactly) or int4 group-wise along the input
    # axis (``quant_group_size`` rows per f32 scale, dequant in-register).
    # Embeddings / norms / lm_head / biases stay high-precision. Decode is
    # weight-bandwidth bound past the KV wins (BASELINE.md roofline), so
    # fewer weight bytes is the direct tok/s lever.
    weight_dtype: str | None = None  # None | 'int8' | 'int4'
    quant_group_size: int = 0  # int4 only; 0 elsewhere
    # Per-config block kinds, one Block definition. ``norm``: 'layer'
    # (LayerNorm, scale and bias, flax's epsilon) or 'rms' (RMSNorm in f32
    # with ``norm_eps``; with ``norm_unit_offset`` the learned vector g
    # enters as 1 + g).
    # ``mlp``: 'gelu' (mlp_in -> GELU -> mlp_out) or 'swiglu'
    # (silu(mlp_gate h) * mlp_up h -> mlp_out). ``residual_dtype``
    # 'float32' keeps the residual stream in f32 while the sublayers compute
    # in ``compute_dtype`` (None: the stream is in ``compute_dtype``).
    # ``fp32_logits`` runs the output head in f32. ``num_pred_heads`` widens
    # the head to that many vocabularies side by side (multi-token
    # prediction heads); head 0 is the next-token head every caller gets.
    norm: str = "layer"  # 'layer' | 'rms'
    norm_eps: float = 1e-6
    norm_unit_offset: bool = False
    mlp: str = "gelu"  # 'gelu' | 'swiglu'
    residual_dtype: str | None = None  # None | 'float32'
    fp32_logits: bool = False
    num_pred_heads: int = 1
    # EVA attention (Zheng et al., arXiv:2302.04542, as EvaByte runs it),
    # on when both are set: positions fall into windows of ``eva_window``;
    # a query attends the exact K/V rows of its own window (causally) and,
    # for every whole chunk of ``eva_chunk`` positions in an EARLIER window,
    # one learned softmax-pooled summary row, all in one softmax
    # (:func:`eva_attention_sublayer`). The serving cache is then two kinds
    # of page, see ``serve/kv_pool.py``.
    eva_window: int | None = None
    eva_chunk: int | None = None
    # Attention in a latent of ``num_heads * head_dim`` where that is not
    # d_model (None: d_model // num_heads). ``tie_embeddings``: the output
    # head is the token embedding (logits = h E^T accumulated in f32, no
    # ``lm_head`` parameters). ``rope_fraction``: the share of each head's
    # dimensions, from the first, that RoPE rotates; the rest pass unrotated.
    head_dim: int | None = None
    tie_embeddings: bool = False
    rope_fraction: float = 1.0
    # Compressed convolutional attention (Zyphra, arXiv:2510.04476), on when
    # both are set (:func:`cca_attention_sublayer`): q and k are projected
    # into the latent, pass a depthwise causal convolution of ``cca_time0``
    # taps and a per-head one of ``cca_time1`` taps, take the q-k mean of the
    # pre-convolution latents, are L2-normalised (k with a learned
    # temperature) and rotated; half of v's heads come from the token before.
    # The serving cache is then K/V pages plus a per-slot state of the last
    # positions' pre-convolution latents, see ``serve/kv_pool.py``.
    cca_time0: int | None = None
    cca_time1: int | None = None
    # Routed experts in place of the block's dense MLP, on when
    # ``num_experts`` > 0 (``models/moe.py``): an MLP router of width
    # ``router_hidden`` over all ``num_experts``, top-1, gated-SiLU experts
    # of width ``expert_width``, no token dropped. The widths lie on the
    # tiles of ``ops/grouped_matmul.py`` or the config is refused.
    # ``experts_held``: the experts THIS chip holds (all by default); the
    # layer routes over all of them and returns the part of the result the
    # held ones give.
    # ``router_hidden`` 0 is the other router the layer has, a linear one:
    # sigmoid scores of one matrix, the ``experts_per_token``
    # largest of score + held bias chosen, their scores renormalised to sum
    # to one and scaled by ``router_scale``.
    # ``expert_act``: 'swiglu' (gate, up, down) or 'relu2' (up, down:
    # relu(.)^2, no gate). ``shared_expert_width`` > 0 adds one always-on
    # expert of that width on every token, unweighted.
    num_experts: int = 0
    router_hidden: int = 0
    expert_width: int = 0
    experts_held: tuple | None = None
    experts_per_token: int = 1
    router_scale: float = 1.0
    expert_act: str = "swiglu"  # 'swiglu' | 'relu2'
    shared_expert_width: int = 0
    # Layers of ONE pre-norm sublayer each (None: every layer is attention
    # then MLP, :class:`Block`): a string of ``num_layers`` kinds, ``M`` a
    # Mamba-2 mixer (``models/mamba.py``), ``E`` the routed expert layer
    # (``models/moe.py``), ``*`` attention (:func:`attention_sublayer`).
    # An ``M`` layer has ``ssm_heads`` heads of ``ssm_head_dim`` channels, a
    # state of ``ssm_state`` a channel, B and C shared by ``ssm_groups``
    # groups of heads, a causal depthwise convolution of ``ssm_conv`` taps,
    # and scans a prefill segment in blocks of ``ssm_block`` positions. The
    # serving cache holds K/V pages for ``*`` layers, the recurrent and
    # convolution state of every slot for ``M`` layers, nothing for ``E``.
    layer_pattern: str | None = None
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_block: int = 128
    # Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 section
    # 2.1), on when ``kv_lora_rank`` > 0 (:func:`mla_attention_sublayer`): a
    # position is cached as ONE row, its normalised latent c
    # (``kv_lora_rank``) and one rotated key part k_pe
    # (``qk_rope_head_dim``) that every head shares; heads of
    # ``qk_nope_head_dim`` + ``qk_rope_head_dim`` for q.k and ``v_head_dim``
    # for v are expanded from c, or the queries are folded into it
    # (absorbed). ``q_lora_rank`` (a low-rank query path) is refused. The
    # rope part takes YaRN where ``yarn_factor`` > 0: frequencies stretched
    # over ``yarn_original_len`` positions between ``yarn_beta_fast`` and
    # ``yarn_beta_slow`` rotations, the tables times
    # m(``yarn_mscale``) / m(``yarn_mscale_all_dim``) and the logits times
    # m(``yarn_mscale_all_dim``)^2, m(x) = 0.1 x ln(factor) + 1
    # (:func:`ops.rope.yarn_mscale`).
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    q_lora_rank: int | None = None
    yarn_factor: float = 0.0
    yarn_original_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # Routed experts behind ``dense_layers`` leading layers whose MLP is the
    # dense one of width ``d_ff`` (DeepSeek's first_k_dense_replace).
    # ``router_score`` 'softmax' and ``router_renorm`` False are the
    # DeepSeek-V2 linear router (``models/moe.route_linear``).
    dense_layers: int = 0
    router_score: str = "sigmoid"  # 'sigmoid' | 'softmax'
    router_renorm: bool = True

    def __post_init__(self):
        # Every string-enum field that SELECTS behavior is validated here:
        # a typo ('Rope', 'rotary') must not silently pick the other path.
        # (attention also accepts callables; kv_cache_dtype None = compute
        # dtype.)
        if self.position not in ("learned", "rope", "none"):
            raise ValueError(
                f"position must be 'learned', 'rope' or 'none', got "
                f"{self.position!r}"
            )
        if self.kv_cache_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_cache_dtype must be None or 'int8', got {self.kv_cache_dtype!r}"
            )
        if self.norm not in ("layer", "rms"):
            raise ValueError(
                f"norm must be 'layer' or 'rms', got {self.norm!r}")
        if self.norm_unit_offset and self.norm != "rms":
            raise ValueError("norm_unit_offset needs norm='rms'")
        if self.mlp not in ("gelu", "swiglu"):
            raise ValueError(
                f"mlp must be 'gelu' or 'swiglu', got {self.mlp!r}")
        if self.residual_dtype not in (None, "float32"):
            raise ValueError(
                f"residual_dtype must be None or 'float32', got "
                f"{self.residual_dtype!r}")
        if self.num_pred_heads < 1:
            raise ValueError(
                f"num_pred_heads must be >= 1, got {self.num_pred_heads}")
        if (self.eva_window is None) != (self.eva_chunk is None):
            raise ValueError("eva_window and eva_chunk go together")
        if self.eva_window is not None:
            w, c = int(self.eva_window), int(self.eva_chunk)
            if c < 1 or w < c or w % c:
                raise ValueError(
                    f"eva_window {w} must be a whole number of eva_chunk {c}")
            if self.attention_window is not None:
                raise EvaUnsupported(
                    "EVA attention has its own windows: attention_window "
                    "must be None")
            if self.kv_heads != self.num_heads:
                raise EvaUnsupported(
                    "EVA attention pools per head: no grouped kv heads")
            if self.kv_cache_dtype is not None:
                raise EvaUnsupported(
                    "kv_cache_dtype='int8' is not extended to EVA's summary "
                    "rows (they would need scale planes of their own)")
            if self.weight_dtype is not None:
                raise EvaUnsupported(
                    "weight-only quantisation is not extended to an EVA "
                    "config")
        if self.head_dim is not None and self.head_dim < 1:
            raise ValueError(f"head_dim must be >= 1, got {self.head_dim}")
        latent = self.head_dim is not None and (
            self.head_dim * self.num_heads != self.d_model)
        if latent and (self.eva or self.weight_dtype is not None):
            raise ValueError(
                f"head_dim {self.head_dim} x {self.num_heads} heads is not "
                f"d_model {self.d_model}: a latent width is not extended to "
                "EVA attention or to weight-only quantisation")
        if not 0.0 < self.rope_fraction <= 1.0 or (
                self.position == "rope" and self.rope_fraction != 1.0
                and (self.rope_dims % 2 or not self.rope_dims)):
            raise ValueError(
                f"rope_fraction {self.rope_fraction} of head_dim {self.dh} "
                "must rotate an even, non-zero number of dimensions")
        if (self.cca_time0 is None) != (self.cca_time1 is None):
            raise ValueError("cca_time0 and cca_time1 go together")
        if self.cca:
            if self.cca_time0 < 1 or self.cca_time1 < 1:
                raise ValueError(
                    f"cca_time0 / cca_time1 must be >= 1, got "
                    f"{self.cca_time0} / {self.cca_time1}")
            kv = self.kv_heads
            if kv < 2 or kv % 2 or self.num_heads % kv:
                raise ValueError(
                    f"CCA shifts the value of half its kv heads by one "
                    f"position: num_kv_heads {kv} must be even and divide "
                    f"num_heads {self.num_heads}")
            for name in ("eva_window", "attention_window", "kv_cache_dtype",
                         "weight_dtype"):
                if getattr(self, name) is not None:
                    raise CcaUnsupported(
                        f"{name} is not extended to a CCA config")
            if self.attention != "dense":
                raise CcaUnsupported(
                    "a CCA config attends with attention='dense' (no flash, "
                    "blockwise or ring path takes its q and k)")
        if self.num_experts:
            if (self.num_experts < 2 or self.expert_width < 1
                    or self.router_hidden < 0):
                raise ValueError(
                    "routed experts need num_experts >= 2, a positive "
                    "expert_width and router_hidden >= 0 (0: the linear "
                    "router, which has no hidden layer)")
            if not 1 <= self.experts_per_token <= self.num_experts:
                raise ValueError(
                    f"experts_per_token {self.experts_per_token} outside "
                    f"[1, num_experts {self.num_experts}]")
            if self.experts_per_token > 1 and self.router_hidden:
                raise ValueError(
                    "the MLP router picks one expert: experts_per_token > 1 "
                    "needs the linear router, router_hidden 0")
            if self.expert_act not in ("swiglu", "relu2"):
                raise ValueError(
                    f"expert_act must be 'swiglu' or 'relu2', got "
                    f"{self.expert_act!r}")
            # The experts' matrices as ``models/moe.py`` lays them.
            d, wide = self.d_model, self.expert_width
            mats = [((wide, d), False),
                    ((d, 2 * wide), False) if self.expert_act == "swiglu"
                    else ((wide, d), True)]
            if not all(grouped_matmul_fits(jax.ShapeDtypeStruct(
                    (1, *shape), self.compute_dtype), t)
                       for shape, t in mats):
                raise ValueError(
                    f"experts of d_model {d} and expert_width {wide} in "
                    f"{jnp.dtype(self.compute_dtype).name} are off the tiles "
                    "of ops/grouped_matmul.py: d_model must be a whole "
                    "number of 128 lanes and expert_width of the dtype's "
                    "sublane rows (of 64 under 'swiglu', whose gate | up is "
                    "2 * expert_width lanes)")
            if self.shared_expert_width < 0:
                raise ValueError("shared_expert_width must be >= 0")
            if self.weight_dtype is not None:
                raise CcaUnsupported(
                    "weight-only quantisation is not extended to routed "
                    "experts")
            if self.dropout_rate:
                raise CcaUnsupported("routed experts have no dropout")
            if self.experts_held is not None:
                held = tuple(int(e) for e in self.experts_held)
                if (not held or list(held) != sorted(set(held))
                        or held[0] < 0 or held[-1] >= self.num_experts):
                    raise ValueError(
                        f"experts_held {self.experts_held} must be distinct "
                        f"ascending ids inside num_experts "
                        f"{self.num_experts}")
                # A list from a JSON file: the config stays hashable.
                object.__setattr__(self, "experts_held", held)
        elif (self.experts_held is not None or self.shared_expert_width
              or self.experts_per_token != 1):
            raise ValueError(
                "experts_held, experts_per_token and shared_expert_width "
                "need num_experts")
        if self.router_score not in ("sigmoid", "softmax"):
            raise ValueError(
                f"router_score must be 'sigmoid' or 'softmax', got "
                f"{self.router_score!r}")
        if (self.router_score != "sigmoid" or not self.router_renorm) and (
                not self.num_experts or self.router_hidden):
            raise ValueError(
                "router_score and router_renorm are the linear router's: "
                "they need num_experts and router_hidden 0")
        if self.dense_layers:
            if (not self.num_experts or self.layer_pattern is not None
                    or not 0 < self.dense_layers < self.num_layers):
                raise ValueError(
                    f"dense_layers {self.dense_layers} must lie in [1, "
                    f"num_layers {self.num_layers}) of a config with routed "
                    "experts and no layer_pattern")
        if self.mla:
            self._check_mla()
        elif self.q_lora_rank is not None or self.yarn_factor:
            raise ValueError(
                "q_lora_rank and yarn_factor are latent attention's: they "
                "need kv_lora_rank > 0")
        if self.layer_pattern is not None:
            pat = self.layer_pattern
            if (not isinstance(pat, str) or len(pat) != self.num_layers
                    or set(pat) - set("ME*")):
                raise ValueError(
                    f"layer_pattern {pat!r} must be num_layers "
                    f"{self.num_layers} kinds, each of 'M', 'E', '*'")
            if "E" in pat and not self.num_experts:
                raise ValueError("an 'E' layer needs num_experts")
            if "M" in pat:
                if min(self.ssm_heads, self.ssm_head_dim, self.ssm_state,
                       self.ssm_groups, self.ssm_block) < 1 or self.ssm_conv < 2:
                    raise ValueError(
                        "an 'M' layer needs positive ssm_heads, ssm_head_dim, "
                        "ssm_state, ssm_groups and ssm_block, and ssm_conv "
                        ">= 2 taps")
                if self.ssm_heads % self.ssm_groups:
                    raise ValueError(
                        f"ssm_groups {self.ssm_groups} must divide ssm_heads "
                        f"{self.ssm_heads}")
            for name in ("eva_window", "cca_time0", "kv_cache_dtype",
                         "weight_dtype", "residual_dtype"):
                if getattr(self, name) is not None:
                    raise SlotStateUnsupported(
                        f"{name} is not extended to a layer_pattern config")
            if self.dropout_rate or self.num_pred_heads != 1:
                raise SlotStateUnsupported(
                    "dropout and prediction heads are not extended to a "
                    "layer_pattern config")
        if self.weight_dtype is not None or self.quant_group_size:
            # Lazy import: quant.py is standalone (flax/jax only), but the
            # module-level import order models/__init__ establishes should
            # not matter for constructing a config.
            from distributed_tensorflow_tpu.models.quant import (
                validate_weight_quant,
            )

            validate_weight_quant(
                self.weight_dtype, self.quant_group_size, self.d_model,
                self.d_ff,
            )

    def _check_mla(self):
        """What latent attention is and is not extended to, by name."""
        if min(self.qk_nope_head_dim, self.qk_rope_head_dim,
               self.v_head_dim) < 1 or self.qk_rope_head_dim % 2:
            raise ValueError(
                "latent attention needs positive qk_nope_head_dim and "
                "v_head_dim and an even, positive qk_rope_head_dim")
        if self.position != "rope":
            raise ValueError("latent attention rotates its rope part: "
                             "position must be 'rope'")
        if self.yarn_factor and (self.yarn_factor <= 1
                                 or self.yarn_original_len < 1):
            raise ValueError(
                f"yarn_factor {self.yarn_factor} must be > 1 with a "
                f"positive yarn_original_len")
        refused = {
            "q_lora_rank": "a low-rank query path (q-LoRA)",
            "kv_cache_dtype": "int8 K/V pages (a latent row has no scale "
                              "plane)",
            "weight_dtype": "weight-only quantisation",
            "num_kv_heads": "grouped kv heads (every head reads the one "
                            "latent row)",
            "attention_window": "a sliding window",
            "eva_window": "EVA attention",
            "cca_time0": "CCA",
            "layer_pattern": "a layer_pattern",
            "residual_dtype": "a float32 residual stream",
        }
        for name, what in refused.items():
            if getattr(self, name) is not None:
                raise SlotStateUnsupported(
                    f"{name}: {what} is not extended to latent attention")
        if self.dropout_rate or self.remat or self.attention != "dense":
            raise SlotStateUnsupported(
                "latent attention is served, not trained: dropout, remat "
                "and the flash, blockwise and ring attention paths are not "
                "extended to it (attention must be 'dense')")

    @property
    def mla(self) -> bool:
        """Whether attention is latent (``kv_lora_rank`` set)."""
        return self.kv_lora_rank > 0

    @property
    def mla_row(self) -> int:
        """Lanes of one position's row in a latent pool: c and k_pe, padded
        to a whole number of 128 lanes (576 -> 640 at DeepSeek-V2's
        widths), so that the paged kernels take a page as it lies."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def mla_scale(self) -> float:
        """The logits' scale: (nope + rope)^-1/2, times YaRN's
        m(``yarn_mscale_all_dim``)^2 where YaRN is on."""
        m = (yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim)
             if self.yarn_factor and self.yarn_mscale_all_dim else 1.0)
        return m * m / float(
            np.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim))

    @property
    def kv_heads(self) -> int:
        return self.num_heads if self.num_kv_heads is None else self.num_kv_heads

    @property
    def eva(self) -> bool:
        return self.eva_window is not None

    @property
    def dh(self) -> int:
        """Width of one attention head."""
        return (self.d_model // self.num_heads if self.head_dim is None
                else self.head_dim)

    @property
    def rope_dims(self) -> int:
        """Leading dimensions of a head that RoPE rotates."""
        return int(round(self.rope_fraction * self.dh))

    @property
    def cca(self) -> bool:
        return self.cca_time0 is not None

    @property
    def cca_hist(self) -> int:
        """Positions of pre-convolution latents a CCA layer needs of the
        tokens before: the two convolutions' reach, and at least the one
        position the value shift reads."""
        return max(1, self.cca_time0 + self.cca_time1 - 2)

    @property
    def cca_state_width(self) -> int:
        """Values one position of the CCA state holds: the pre-convolution
        q and k latents and the shifted half of v's projection."""
        return (self.num_heads + self.kv_heads + self.kv_heads // 2) * self.dh

    @property
    def ssm(self) -> bool:
        """Whether a layer holds a recurrent state."""
        return self.layer_pattern is not None and "M" in self.layer_pattern

    @property
    def ssm_inner(self) -> int:
        """Channels of a Mamba-2 mixer: heads x head width."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """Channels the mixer's convolution runs over: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def expert_layers(self) -> int:
        """Layers with routed experts."""
        if not self.num_experts:
            return 0
        return (self.num_layers - self.dense_layers
                if self.layer_pattern is None
                else self.layer_pattern.count("E"))

    @property
    def held(self) -> tuple:
        """The experts this chip holds (all of them by default)."""
        return (tuple(range(self.num_experts)) if self.experts_held is None
                else self.experts_held)


def quantize_kv_rows(x):
    """Symmetric absmax int8 quantization over the last (head_dim) axis:
    ``x`` (..., dh) → (int8 values (..., dh), f32 scales (...)). The scale
    is per ROW (one per cached key/value vector), so it factors out of any
    dot product over dh exactly — consumers apply it to the score/weight
    matrices instead of dequantizing the cache."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.round(xf / scale[..., None]).astype(jnp.int8)
    return q, scale


def matmul_dense(cfg: TransformerConfig, features: int, name: str):
    """The four per-block matmul projections (``qkv``/``proj``/``mlp_in``/
    ``mlp_out``) route through here so ``cfg.weight_dtype`` can swap them
    for weight-only-quantized layers (``models/quant.py::QuantDense`` —
    int values + f32 scales, dequant fused into the forward). Embeddings,
    norms, and ``lm_head`` never do: they are a small fraction of the
    bytes and dominate quality sensitivity."""
    if getattr(cfg, "weight_dtype", None):
        from distributed_tensorflow_tpu.models.quant import QuantDense

        return QuantDense(
            features, mode=cfg.weight_dtype,
            group_size=cfg.quant_group_size, dtype=cfg.compute_dtype,
            use_bias=cfg.use_bias, name=name,
        )
    return nn.Dense(
        features, dtype=cfg.compute_dtype, name=name, use_bias=cfg.use_bias
    )


class RMSNorm(nn.Module):
    """x / sqrt(mean(x^2) + eps) * w in float32, cast to ``dtype``; with
    ``unit_offset`` the learned vector g (``scale``) enters as w = 1 + g."""

    eps: float = 1e-6
    unit_offset: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.unit_offset else nn.initializers.ones
        g = self.param("scale", init, (x.shape[-1],)).astype(jnp.float32)
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + self.eps)
        return (y * (1.0 + g if self.unit_offset else g)).astype(self.dtype)


def block_norm(cfg, name: str):
    """The norm layer ``cfg.norm`` names, declared under ``name`` on the
    calling ``@nn.compact`` module."""
    if getattr(cfg, "norm", "layer") == "rms":
        return RMSNorm(eps=cfg.norm_eps, unit_offset=cfg.norm_unit_offset,
                       dtype=cfg.compute_dtype, name=name)
    return nn.LayerNorm(dtype=cfg.compute_dtype, name=name)


def _f32_norm(cfg, name: str):
    """:func:`block_norm` computed and handed on in float32: what a router
    reads."""
    if cfg.norm == "rms":
        return RMSNorm(eps=cfg.norm_eps, unit_offset=cfg.norm_unit_offset,
                       name=name)
    return nn.LayerNorm(dtype=jnp.float32, name=name)


def _attention_fn(cfg: TransformerConfig, prefer_packed: bool = False) -> Callable:
    """Resolve ``cfg.attention`` to a callable. The callable's optional
    ``input_layout`` attribute ("bhsd" default, "bshd", or "packed_qkv")
    tells :func:`attention_sublayer` which layout to feed it.

    ``prefer_packed`` opts the flash path into the layout-native
    packed-qkv kernels — the attend fn then takes the fused
    (B, S, (H + 2·KV)·head_dim) qkv projection output directly (equal
    thirds for MHA), so no q/k/v slice copies or head transposes
    materialize at the Pallas custom-call boundary
    (~10 ms/step on the flagship, XPlane r4). Only callers that route
    through :func:`attention_sublayer` may pass it (TransformerLM, the MoE
    block, the pipeline stages); direct (q, k, v) consumers like TpBlock
    keep the default 3-arg BHSD callable."""
    if callable(cfg.attention):
        return cfg.attention
    w = getattr(cfg, "attention_window", None)
    if cfg.attention == "dense":
        return lambda q, k, v: A.dense_attention(q, k, v, causal=True, window=w)
    if cfg.attention == "blockwise":
        return lambda q, k, v: A.blockwise_attention(q, k, v, causal=True, window=w)
    if cfg.attention == "flash":
        if prefer_packed:
            # GQA-aware: the kernel's kv column index maps share kv heads
            # across query groups directly — no expanded K/V materializes.
            # RoPE-aware: cos/sin tables pass straight through to the
            # kernels, which rotate q/k tiles in VMEM (ops/attention.py) —
            # no rotated copies of the projection output exist in HBM.
            def fn(qkv, rope_cos=None, rope_sin=None, rope_theta=None):
                return A.flash_attention_qkv(
                    qkv, cfg.num_heads, cfg.num_kv_heads, causal=True, window=w,
                    rope_cos=rope_cos, rope_sin=rope_sin, rope_theta=rope_theta,
                )

            fn.input_layout = "packed_qkv"
            return fn
        return lambda q, k, v: A.flash_attention(q, k, v, causal=True, window=w)
    raise ValueError(f"unknown attention implementation: {cfg.attention!r}")


def _accepts_rope_tables(attend) -> bool:
    """Feature-detect rope kwargs on a packed-layout attend callable: the
    in-repo packed fn takes them (in-kernel rotation); an EXTERNAL callable
    tagged input_layout='packed_qkv' that predates rope gets the outside-
    rotation fallback instead of a TypeError."""
    import inspect

    try:
        params = inspect.signature(attend).parameters
    except (TypeError, ValueError):  # builtins/partials without signatures
        return False
    # Require the EXPLICIT parameter: a legacy `**kwargs` wrapper would
    # swallow the tables and silently attend over unrotated q/k — worse
    # than the outside-rotation fallback it would bypass.
    return "rope_cos" in params


def attention_sublayer(cfg, x, attend, train: bool = False, cache=None,
                       positions=None, self_mask=None):
    """Pre-norm self-attention + residual, shared by :class:`Block` and the
    MoE block (``parallel/expert_parallel.py``). MUST be called from inside
    an ``@nn.compact`` module body — layers are declared with fixed names
    (``ln1``/``qkv``/``proj``) on the CALLING module, so extracting this
    helper changed no parameter tree. Returns ``(x, cache)`` (cache None on
    the plain path).

    ``positions`` (B, S) global token positions — only consumed when
    ``cfg.position == 'rope'`` (the q/k head rotation needs them; sequence
    shards pass their global positions, same contract as ``pos_embed``).
    None defaults to ``arange(S)`` offset by the cache's filled length.

    ``self_mask`` (S, S) bool — cached path only: the fed block is a TREE
    of speculative drafts, not a chain, so cache WRITE order within the
    block is not causal order. Query ``q`` attends the committed prefix
    (cache positions below ``len``) plus exactly the in-block entries
    ``self_mask[q]`` marks True (its tree ancestors, self inclusive);
    positions at or past ``len + S`` stay masked (stale junk). The callers
    pass tree-semantic ``positions`` alongside, so rotations/embeddings
    follow tree DEPTH while cache offsets follow write order.

    A cache with a ``pages`` entry is the serving engine's page pool seen
    through its page tables (:func:`_attend_through_table`): one new token
    per slot, each slot at its own length. It shares nothing with the dense
    cached branch below."""
    if getattr(cfg, "eva", False):
        raise EvaUnsupported(
            "an EVA config attends through eva_attention_sublayer (Block); "
            "this block kind has no EVA path")
    if getattr(cfg, "cca", False):
        raise CcaUnsupported(
            "a CCA config attends through cca_attention_sublayer (Block); "
            "this block kind has no CCA path")
    h = block_norm(cfg, "ln1")(x)
    b, s, _ = h.shape
    dh = getattr(cfg, "dh", None) or cfg.d_model // cfg.num_heads
    qw = cfg.num_heads * dh  # the latent's width: d_model unless head_dim says
    kv = cfg.kv_heads
    if not (1 <= kv <= cfg.num_heads) or cfg.num_heads % kv:
        raise ValueError(
            f"num_kv_heads must be in [1, num_heads] and divide it: "
            f"num_heads {cfg.num_heads} not divisible by num_kv_heads {kv}"
        )
    group = cfg.num_heads // kv
    # GQA shrinks the fused projection: [q (H·dh) | k (KV·dh) | v (KV·dh)].
    qkv = matmul_dense(cfg, qw + 2 * kv * dh, "qkv")(h)

    rope = getattr(cfg, "position", "learned") == "rope"
    layout = getattr(attend, "input_layout", "bhsd")
    if rope:
        # One cos/sin table per sublayer call; XLA CSEs the identical
        # tables across layers. (1, S, half) or (B, S, half), f32.
        # The packed path hands these INTO the kernels as operands
        # ("tables" mode) — the kernels' other option, computing cos/sin
        # from in-kernel iotas ("iota" mode, rope_theta=), measured TEN
        # MFU points slower on the flagship (62.1 vs 72.7%): Mosaic's
        # per-tile cos/sin transcendentals cost far more than the table
        # DMA they save (BASELINE.md r5 negative result).
        rot = getattr(cfg, "rope_dims", dh)
        if rot != dh and layout == "packed_qkv":
            raise ValueError(
                "rope_fraction < 1 is not extended to the packed flash "
                "kernels, which rotate whole heads")
        cos, sin = rope_tables(
            rot, s, cfg.rope_theta, positions=positions,
            start=cache["len"] if cache is not None else 0,
        )

    def split_qkv():
        return jnp.split(qkv, [qw, qw + kv * dh], axis=-1)

    def expand_kv(t4):
        # (B, S, KV, dh) -> (B, S, H, dh): each query-head group reads its
        # shared kv head (materialized repeat — the non-packed tiers want
        # H-headed operands).
        if group == 1:
            return t4
        return jnp.repeat(t4, group, axis=2)

    if cache is None and layout == "packed_qkv":
        # Layout-native attention: the attend fn consumes the fused qkv
        # projection output DIRECTLY — neither the q/k/v slice copies nor
        # the (B,H,S,D) head transposes ever materialize at the kernel
        # boundary (measured ~10 ms/step of boundary passes on the
        # flagship, XPlane r4 — ops/attention.py packed-qkv section).
        # GQA included: the kernel's kv column index maps share kv heads
        # across query groups, so the narrower [q|k|v] projection passes
        # through unexpanded. Rope happens INSIDE the kernels too (q/k
        # tiles rotate in VMEM, gradients rotate back in VMEM) — the
        # outside rotation (split → apply_rope → concat) measured
        # ~7 ms/layer of materialized boundary passes at the flagship
        # shape (XLA cannot fuse elementwise work into a Pallas custom
        # call's operands): 60.7 → 72.7% flagship MFU (BASELINE.md r5).
        if rope and _accepts_rope_tables(attend):
            # Table precision follows compute precision: under bf16 compute
            # the q/k tiles round to bf16 after rotation anyway, and bf16
            # tables halve the kernels' per-tile table DMA — measured
            # 11.00 → 10.36 ms on the flagship-shape packed fwd+bwd
            # (no-rope floor 9.00; BASELINE.md r5). f32 compute keeps f32
            # tables (and the f32 parity tolerances).
            tdt = (
                jnp.bfloat16
                if cfg.compute_dtype == jnp.bfloat16
                else cos.dtype
            )
            attn = attend(qkv, rope_cos=cos.astype(tdt), rope_sin=sin.astype(tdt))
        elif rope:
            # EXTERNAL packed-layout callable without the rope kwargs:
            # rotate outside (slower — the boundary passes the in-kernel
            # path exists to avoid — but the extension contract keeps
            # working).
            q, k, v = split_qkv()
            q = apply_rope(q.reshape(b, s, cfg.num_heads, dh), cos, sin)
            k = apply_rope(k.reshape(b, s, kv, dh), cos, sin)
            attn = attend(
                jnp.concatenate(
                    [q.reshape(b, s, qw),
                     k.reshape(b, s, kv * dh), v],
                    axis=-1,
                )
            )
        else:
            attn = attend(qkv)
    elif cache is None and layout == "bshd":
        # Extension point for EXTERNAL attend callables tagged
        # input_layout="bshd" (the public flash_attention_bshd layout) —
        # no in-repo _attention_fn path hands this out since the packed
        # kernels went GQA-native. (B, S, H, dh) is a FREE reshape of the
        # split slices (kv heads expand by repeat under GQA); no head
        # transposes materialize.
        q, k, v = split_qkv()
        qh = q.reshape(b, s, cfg.num_heads, dh)
        kh = k.reshape(b, s, kv, dh)
        if rope:
            qh = apply_rope(qh, cos, sin)
            kh = apply_rope(kh, cos, sin)  # pre-expand: kv heads rotate once
        kh = expand_kv(kh)
        vh = expand_kv(v.reshape(b, s, kv, dh))
        attn = attend(qh, kh, vh).reshape(b, s, qw)
    elif cache is None:
        q, k, v = split_qkv()
        qh = q.reshape(b, s, cfg.num_heads, dh)
        kh = k.reshape(b, s, kv, dh)
        if rope:
            qh = apply_rope(qh, cos, sin)
            kh = apply_rope(kh, cos, sin)
        # (B, S, n, dh) -> (B, n, S, dh)
        attn = attend(
            qh.transpose(0, 2, 1, 3),
            expand_kv(kh).transpose(0, 2, 1, 3),
            expand_kv(v.reshape(b, s, kv, dh)).transpose(0, 2, 1, 3),
        )
        attn = attn.transpose(0, 2, 1, 3).reshape(b, s, qw)
    elif "pages" in cache:
        q, k, v = split_qkv()
        q4 = q.reshape(b, s, cfg.num_heads, dh)
        k4 = k.reshape(b, s, kv, dh)
        if rope:
            q4 = apply_rope(q4, cos, sin)
            k4 = apply_rope(k4, cos, sin)
        attn, cache = _attend_through_table(
            cfg, cache, q4, k4, v.reshape(b, s, kv, dh)
        )
    else:
        q, k, v = split_qkv()
        q4 = q.reshape(b, s, cfg.num_heads, dh)
        k4 = k.reshape(b, s, kv, dh)
        if rope:
            # Rotate at ABSOLUTE positions (cache['len'] + arange(s) via
            # the cos/sin tables above); the cache stores post-rotation
            # keys, so earlier entries never need re-rotating.
            q4 = apply_rope(q4, cos, sin)
            k4 = apply_rope(k4, cos, sin)
        to_heads = lambda t4: t4.transpose(0, 2, 1, 3)
        # Cached decode (s tokens: 1 for the sampling loop, the whole
        # prompt for prefill): append K/V at offset `len`, causally
        # attend over prefix + self. The cache stores the UNEXPANDED
        # (B, KV, S_max, dh) heads — under GQA that is the whole point:
        # decode is KV-bandwidth bound past small batches (BASELINE.md
        # decode roofline) and the cache shrinks by the group factor.
        # f32 accumulation like ops.attention.dense_attention; NEG_INF
        # (not -inf) keeps fully-masked softmax rows NaN-free.
        quant = getattr(cfg, "kv_cache_dtype", None)
        if quant not in (None, "int8"):
            raise ValueError(f"kv_cache_dtype must be None or 'int8', got {quant!r}")
        kh, vh = to_heads(k4), to_heads(v.reshape(b, s, kv, dh))
        if quant == "int8":
            # Per-row symmetric quantization on WRITE; the scales ride the
            # cache as (B, KV, S) f32 (S minor — no lane-padding tax). The
            # dot products below consume the int8 cache directly and apply
            # the scales to the score/weight matrices — the row scale
            # factors out of the dh contraction exactly, so no dequantized
            # (B, KV, S, dh) tensor ever re-materializes in HBM.
            kh, k_sc = quantize_kv_rows(kh)
            vh, v_sc = quantize_kv_rows(vh)
            k_scale = jax.lax.dynamic_update_slice(
                cache["k_scale"], k_sc, (0, 0, cache["len"])
            )
            v_scale = jax.lax.dynamic_update_slice(
                cache["v_scale"], v_sc, (0, 0, cache["len"])
            )
        ks = jax.lax.dynamic_update_slice(
            cache["k"], kh, (0, 0, cache["len"], 0)
        )
        vs = jax.lax.dynamic_update_slice(
            cache["v"], vh, (0, 0, cache["len"], 0)
        )
        if cache.get("flash") and self_mask is None and quant != "int8":
            # A prefill chunk on an engine whose shapes the kernel takes
            # (``SlotEngine.prefill_path``): the block of query rows at the
            # traced offset ``len`` attends the live keys by blocks. No
            # (s, S_max) scores, no expanded kv heads, no dead key read.
            attn = A.chunk_flash_attention(
                to_heads(q4), ks, vs, cache["len"],
                window=getattr(cfg, "attention_window", None),
            )
        else:
            # The verify programs (self_mask), the int8 cache, the gather
            # path's one-row decode and every off-tile shape: dense over
            # all S_max positions.
            qh = to_heads(q4).reshape(b, kv, group, s, dh)
            scores = jnp.einsum(
                "bkgqd,bkTd->bkgqT",
                qh,
                ks.astype(cfg.compute_dtype) if quant == "int8" else ks,
                preferred_element_type=jnp.float32,
            ) / np.sqrt(dh)
            if quant == "int8":
                scores = scores * k_scale[:, :, None, None, :]
            q_pos = cache["len"] + jnp.arange(s)  # (s,)
            key_pos = jnp.arange(ks.shape[2])  # (S_max,)
            if self_mask is not None:
                # Tree-speculation verify: in-block keys are gated by the
                # static ancestor mask (write order != causal order inside the
                # block), the committed prefix is fully visible, and stale
                # rows past the block stay hidden. attention_window cannot
                # compose with a tree block (positions are non-monotone in
                # write order) — the serving engine rejects that pairing at
                # construction.
                if getattr(cfg, "attention_window", None) is not None:
                    raise ValueError(
                        "self_mask (tree attention) is incompatible with "
                        "attention_window"
                    )
                in_block = (key_pos[None, :] >= cache["len"]) & (
                    key_pos[None, :] < cache["len"] + s
                )
                rel = jnp.clip(key_pos - cache["len"], 0, s - 1)
                allowed = (key_pos[None, :] < cache["len"]) | (
                    in_block & self_mask[:, rel]
                )
            else:
                allowed = key_pos[None, :] <= q_pos[:, None]  # (s, S_max)
                if getattr(cfg, "attention_window", None) is not None:
                    allowed &= (
                        key_pos[None, :] > q_pos[:, None] - cfg.attention_window
                    )
            scores = jnp.where(allowed[None, None, None, :, :], scores, A.NEG_INF)
            weights = jax.nn.softmax(scores, -1)
            if quant == "int8":
                weights = weights * v_scale[:, :, None, None, :]
            attn = jnp.einsum(
                "bkgqT,bkTd->bkgqd", weights, vs.astype(jnp.float32)
            ).astype(cfg.compute_dtype)
            attn = attn.reshape(b, cfg.num_heads, s, dh)
        attn = attn.transpose(0, 2, 1, 3).reshape(b, s, qw)
        cache = {"k": ks, "v": vs, "len": cache["len"] + s}
        if quant == "int8":
            cache["k_scale"] = k_scale
            cache["v_scale"] = v_scale
    attn = matmul_dense(cfg, cfg.d_model, "proj")(attn)
    if cfg.dropout_rate:
        attn = nn.Dropout(cfg.dropout_rate, deterministic=not train)(attn)
    return x + attn, cache


def _attend_through_table(cfg, cache, q4, k4, v4):
    """The decode round on a page pool: ``cache`` holds one layer's pool
    leaves ``k`` / ``v`` as they lie, (pages, kv, page_size, dh), and, for
    every slot of the batch, ``len`` (the position of its one new token),
    ``pages`` (its row of the page table), ``write_page`` (the physical page
    its new row goes to: the engine points a masked lane at its trash page)
    and ``attend`` (how many positions it attends: ``len + 1``, 0 for a
    masked lane). The new K and V row is written straight into the pool
    (:func:`_write_rows`: a page copy a lane that attends, in place), then
    ``ops.attention.paged_decode_attention`` reads the live pages
    where they lie: no (B, kv, S_max, dh) cache is gathered or written
    back. ``q4`` (B, 1, H, dh) and ``k4`` / ``v4`` (B, 1, kv, dh) are already
    rotated. Returns ``(attn (B, 1, d_model), cache with the new leaves)``."""
    b, s, heads, dh = q4.shape
    if s != 1:
        raise ValueError(f"a paged cache takes one token per slot, got {s}")
    kv, ps = cache["k"].shape[1:3]
    ks, vs = _write_rows(cache["k"], cache["v"], k4[:, 0], v4[:, 0],
                         cache["write_page"], cache["len"] % ps,
                         cache["attend"] > 0)
    q = q4[:, 0].reshape(b, kv, heads // kv, dh)
    if A.paged_decode_fits(ks):
        attn = A.paged_decode_attention(
            q, ks, vs, cache["pages"], cache["attend"],
            window=getattr(cfg, "attention_window", None),
        )
    else:
        # Only a config that always decodes through the table comes here
        # (CCA: its state makes the gather path's per-slot vmap a second
        # program): the engine sends every other one down the gather path.
        attn = _table_attention_sum(q, ks, vs, cache["pages"], cache["attend"])
    return attn.reshape(b, 1, heads * dh), dict(cache, k=ks, v=vs)


def _write_rows(k_leaf, v_leaf, k_rows, v_rows, page, offset, live):
    """Row ``offset[b]`` of every kv head of page ``page[b]`` of a layer's
    pool leaves (pages, kv, page_size, dh) set to ``k_rows[b]`` /
    ``v_rows[b]`` (B, kv, dh), for the lanes ``live`` marks. Where the
    leaves fit the paged kernels (:func:`ops.attention.paged_decode_fits`)
    the page-copy kernel ``paged_row_write`` writes them in place and a lane
    that is not live writes nothing. Elsewhere (a page or head size off the
    chip's tiles: the CPU tests) a row scatter on the leaf seen as rows of
    dh writes every lane, a masked one where its page points (the engine's
    trash page). Indexing (page, :, offset) instead would make XLA:TPU relay
    the whole pool out around every write."""
    if A.paged_decode_fits(k_leaf):
        return A.paged_row_write(k_leaf, v_leaf, k_rows, v_rows, page, offset,
                                 live)
    pages, kv, ps, dh = k_leaf.shape
    rows = ((page[:, None] * kv + jnp.arange(kv)[None, :]) * ps
            + offset[:, None]).reshape(-1)

    def write(leaf, new):
        flat = leaf.reshape(pages * kv * ps, dh)
        return flat.at[rows].set(
            new.reshape(-1, dh).astype(leaf.dtype)).reshape(leaf.shape)

    return write(k_leaf, k_rows), write(v_leaf, v_rows)


def _table_attention_sum(q, ks, vs, tables, attend, scale=None):
    """What ``paged_decode_attention`` computes, in ``jax.numpy``, for pool
    leaves the kernel does not take (a page or head size off the chip's
    tiles: the CPU tests): ``q`` (B, kv, group, dh) over the rows of
    ``tables`` (B, pages), the first ``attend`` (B,) of them live; scores
    times ``scale`` (dh^-1/2 by default)."""
    b, kv, _, dh = q.shape
    rows_k = ks[tables].transpose(0, 2, 1, 3, 4).reshape(b, kv, -1, dh)
    rows_v = vs[tables].transpose(0, 2, 1, 3, 4).reshape(b, kv, -1, dh)
    live = jnp.arange(rows_k.shape[2])[None, :] < attend[:, None]
    scores = jnp.einsum(
        "bkgd,bkTd->bkgT", q, rows_k, preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(dh) if scale is None else scores * scale
    live = live[:, None, None, :]
    weights = jnp.where(
        live, jax.nn.softmax(jnp.where(live, scores, A.NEG_INF), -1), 0.0)
    return jnp.einsum(
        "bkgT,bkTd->bkgd", weights, rows_v.astype(jnp.float32)
    ).astype(q.dtype)


def eva_summaries(k, v, phi, mu):
    """EVA's chunk summaries. ``k`` / ``v`` (..., H, C, dh): the (rotated)
    key and value rows of one chunk per leading index; ``phi`` / ``mu`` (H,
    dh). With alpha = softmax over the chunk's C rows of dh^-1/2 * k.phi:
    k~ = sum alpha k + mu and v~ = sum alpha v, each (..., H, dh) in f32."""
    kf = k.astype(jnp.float32)
    scores = jnp.einsum(
        "...hcd,hd->...hc", kf, phi.astype(jnp.float32)
    ) / np.sqrt(k.shape[-1])
    alpha = jax.nn.softmax(scores, -1)
    ks = jnp.einsum("...hc,...hcd->...hd", alpha, kf) + mu.astype(jnp.float32)
    vs = jnp.einsum("...hc,...hcd->...hd", alpha, v.astype(jnp.float32))
    return ks, vs


def _eva_dense(cfg, qh, kh, vh, phi, mu):
    """EVA attention of a whole sequence from position 0, no cache: ``qh`` /
    ``kh`` / ``vh`` (B, H, T, dh), rotated. Query i attends the rows j <= i
    of its own window and the summary of every whole chunk that lies in an
    earlier window, in one softmax. O(T^2) scores: the training and test
    path; serving composes the same rows from pages."""
    b, heads, t, dh = qh.shape
    w, c = int(cfg.eva_window), int(cfg.eva_chunk)
    n = t // c
    pos = jnp.arange(t)
    own = (pos[None, :] <= pos[:, None]) & (
        pos[None, :] // w == pos[:, None] // w)
    scores = jnp.einsum(
        "bhqd,bhTd->bhqT", qh, kh, preferred_element_type=jnp.float32
    ) / np.sqrt(dh)
    scores = jnp.where(own[None, None], scores, A.NEG_INF)
    vals = vh.astype(jnp.float32)
    if n:
        ks, vs = eva_summaries(
            kh[:, :, : n * c].reshape(b, heads, n, c, dh).transpose(0, 2, 1, 3, 4),
            vh[:, :, : n * c].reshape(b, heads, n, c, dh).transpose(0, 2, 1, 3, 4),
            phi, mu,
        )  # (B, n, H, dh)
        ks = ks.transpose(0, 2, 1, 3).astype(kh.dtype)
        vs = vs.transpose(0, 2, 1, 3).astype(vh.dtype)
        earlier = (jnp.arange(n)[None, :] * c) // w < pos[:, None] // w
        s_sum = jnp.einsum(
            "bhqd,bhnd->bhqn", qh, ks, preferred_element_type=jnp.float32
        ) / np.sqrt(dh)
        scores = jnp.concatenate(
            [jnp.where(earlier[None, None], s_sum, A.NEG_INF), scores], -1)
        vals = jnp.concatenate([vs.astype(jnp.float32), vals], 2)
    weights = jax.nn.softmax(scores, -1)
    return jnp.einsum("bhqT,bhTd->bhqd", weights, vals).astype(cfg.compute_dtype)


def _eva_through_table(cfg, cache, q4, k4, v4, phi, mu):
    """The EVA decode round on the page pool. ``cache`` is as in
    :func:`_attend_through_table`, where a slot's table row is COMPOSED
    [summary pages of its finished windows | pages of its current window]
    (``serve/kv_pool.py``), so ``attend`` counts summaries and window rows
    alike and ``write_page`` is the current window's page under ``len``;
    and ``sum_page``: the physical page the summary of the chunk that this
    token completes goes to (the slot's forming summary page; the trash page
    when the token completes no chunk). A chunk is one page: after the new
    row is written the page is read back, pooled (:func:`eva_summaries`) and
    its summary row written at ``(len % window) // chunk`` modulo the page.
    Both writes go through :func:`_write_rows`: the page-copy kernel where
    the leaves fit it, which writes the summary of a lane that fills its
    chunk and nothing else. Attention is ``paged_decode_attention`` over
    the composed table where the pool's leaves fit it (one query row a kv
    head: the kernel's row form, in chunks of 1 MiB a buffer), and the same
    sum in ``jax.numpy`` where they do not (a page or head size off the
    chip's tiles: the CPU tests)."""
    b, s, heads, dh = q4.shape
    if s != 1:
        raise ValueError(f"a paged cache takes one token per slot, got {s}")
    kv, ps = cache["k"].shape[1:3]
    w, c = int(cfg.eva_window), int(cfg.eva_chunk)
    if c != ps:
        raise EvaUnsupported(f"eva_chunk {c} must be the page size {ps}")
    live = cache["attend"] > 0
    ks, vs = _write_rows(cache["k"], cache["v"], k4[:, 0], v4[:, 0],
                         cache["write_page"], cache["len"] % ps, live)
    with jax.named_scope("eva.summary"):
        sk, sv = eva_summaries(
            ks[cache["write_page"]], vs[cache["write_page"]], phi, mu)
        row = ((cache["len"] % w) // c) % ps
        # Only a token that fills its chunk has a summary to keep (a lane in
        # 16 a round at EvaByte's chunk): the page kernel writes those lanes
        # alone; the scatter writes every lane, the rest into the trash page.
        fills = live & ((cache["len"] + 1) % c == 0)
        ks, vs = _write_rows(ks, vs, sk, sv, cache["sum_page"], row, fills)
    q = q4[:, 0].reshape(b, kv, 1, dh)
    if A.paged_decode_fits(ks):
        # 32 kv heads make a page 16 times StarCoder2's: the kernel sizes
        # its chunk by the page's bytes (8 pages here, 1 MiB a buffer).
        attn = A.paged_decode_attention(
            q, ks, vs, cache["pages"], cache["attend"])
    else:
        attn = _table_attention_sum(q, ks, vs, cache["pages"], cache["attend"])
    return attn.reshape(b, 1, heads * dh), dict(cache, k=ks, v=vs)


def eva_attention_sublayer(mod, cfg, x, train: bool = False, cache=None,
                           positions=None):
    """Pre-norm EVA self-attention + residual, beside
    :func:`attention_sublayer`; called from ``mod``'s ``@nn.compact`` body
    (layers ``ln1`` / ``qkv`` / ``proj`` and the per-head vectors
    ``eva_phi`` / ``eva_mu`` are declared on ``mod``). Three paths:

    * ``cache=None``: the whole sequence from position 0 (:func:`_eva_dense`).
    * a cache with ``pages``: the serving engine's decode round through its
      composed page table (:func:`_eva_through_table`).
    * a dense cache ``{'k','v','len','win_base'}``: one prefill segment of the
      serving engine. The cache is a slot's LOGICAL rows, gathered from its
      composed table: summaries of finished windows, then the current
      window's rows, ``len`` of them filled; the segment lies inside the
      current window, so it appends at ``len`` and attends causally over
      logical rows, which is EVA's set exactly. ``positions`` (absolute, for
      the rotation) must be given: logical offsets are not positions. The
      returned layer carries ``sum_k`` / ``sum_v`` (B, H, window/chunk, dh):
      the summary of every chunk of the current window's region
      (``win_base`` onward) after the append, for the engine to write those
      of whole chunks into the slot's forming summary pages."""
    h = block_norm(cfg, "ln1")(x)
    b, s, _ = h.shape
    heads = cfg.num_heads
    dh = cfg.d_model // heads
    qkv = matmul_dense(cfg, 3 * cfg.d_model, "qkv")(h)
    vec_init = nn.initializers.normal(dh ** -0.5)
    phi = mod.param("eva_phi", vec_init, (heads, dh))
    mu = mod.param("eva_mu", vec_init, (heads, dh))
    if cache is not None and "pages" not in cache and positions is None:
        raise EvaUnsupported(
            "a monolithic cache does not compose EVA's summaries and "
            "windows: serve an EVA config through SlotEngine's page pool")
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q4 = q.reshape(b, s, heads, dh)
    k4 = k.reshape(b, s, heads, dh)
    v4 = v.reshape(b, s, heads, dh)
    if getattr(cfg, "position", "learned") == "rope":
        cos, sin = rope_tables(dh, s, cfg.rope_theta, positions=positions)
        q4 = apply_rope(q4, cos, sin)
        k4 = apply_rope(k4, cos, sin)
    to_heads = lambda t4: t4.transpose(0, 2, 1, 3)
    if cache is None:
        attn = to_heads(_eva_dense(
            cfg, to_heads(q4), to_heads(k4), to_heads(v4), phi, mu))
        attn = attn.reshape(b, s, cfg.d_model)
    elif "pages" in cache:
        attn, cache = _eva_through_table(cfg, cache, q4, k4, v4, phi, mu)
    else:
        w, c = int(cfg.eva_window), int(cfg.eva_chunk)
        ks = jax.lax.dynamic_update_slice(
            cache["k"], to_heads(k4), (0, 0, cache["len"], 0))
        vs = jax.lax.dynamic_update_slice(
            cache["v"], to_heads(v4), (0, 0, cache["len"], 0))
        scores = jnp.einsum(
            "bhqd,bhTd->bhqT", to_heads(q4), ks,
            preferred_element_type=jnp.float32,
        ) / np.sqrt(dh)
        q_pos = cache["len"] + jnp.arange(s)
        allowed = jnp.arange(ks.shape[2])[None, :] <= q_pos[:, None]
        weights = jax.nn.softmax(
            jnp.where(allowed[None, None], scores, A.NEG_INF), -1)
        attn = jnp.einsum(
            "bhqT,bhTd->bhqd", weights, vs.astype(jnp.float32)
        ).astype(cfg.compute_dtype)
        attn = to_heads(attn).reshape(b, s, cfg.d_model)
        with jax.named_scope("eva.summary"):
            region = lambda t: jax.lax.dynamic_slice(
                t, (0, 0, cache["win_base"], 0), (b, heads, w, dh)
            ).reshape(b, heads, w // c, c, dh).transpose(0, 2, 1, 3, 4)
            sk, sv = eva_summaries(region(ks), region(vs), phi, mu)
        cache = {"k": ks, "v": vs,
                 "sum_k": sk.transpose(0, 2, 1, 3).astype(ks.dtype),
                 "sum_v": sv.transpose(0, 2, 1, 3).astype(vs.dtype),
                 "len": cache["len"] + s}
    attn = matmul_dense(cfg, cfg.d_model, "proj")(attn)
    if cfg.dropout_rate:
        attn = nn.Dropout(cfg.dropout_rate, deterministic=not train)(attn)
    return x + attn, cache


def _l2_heads(x, dh):
    """Each head of ``x`` (..., dh) f32 scaled to length sqrt(dh)."""
    return x * (np.sqrt(dh) * jax.lax.rsqrt(
        jnp.sum(x * x, -1, keepdims=True) + 1e-6))


def cca_attention_sublayer(mod, cfg, x, train: bool = False, cache=None,
                           positions=None):
    """Pre-norm compressed convolutional attention (CCA, arXiv:2510.04476,
    as ZAYA1 runs it) + residual, beside :func:`attention_sublayer`; called
    from ``mod``'s ``@nn.compact`` body. With H query heads, G kv heads of
    ``dh`` and h = norm(x):

    1. one projection ``cca_in`` gives the latents q~ (H dh), k~ (G dh) and
       the two halves of the value, ``va`` (kv heads 0 .. G/2-1, from this
       token) and ``vb`` (kv heads G/2 .. G-1): ``v_t = [va_t ; vb_{t-1}]``;
    2. q~ and k~ pass two causal convolutions over positions: depthwise
       (``cca_conv0`` (H+G) dh x time0), then per head (``cca_conv1`` (H+G) x
       time1 x dh x dh, mixing a head's channels); positions before 0 are 0;
    3. the q-k mean of the PRE-convolution latents is added: query head n of
       kv group g gets (q~[n] + k~[g]) / 2, kv head g gets (mean_n q~[n] +
       k~[g]) / 2;
    4. every head of q and k is scaled to length sqrt(dh), k times the
       learned temperature ``cca_temp`` (G,); RoPE on the leading
       ``cfg.rope_dims`` dimensions;
    5. causal attention in the latent (GQA), ``proj`` back to d_model.

    What steps 1-3 need of the tokens before is the STATE: the last
    ``cfg.cca_hist`` positions' [q~ | k~ | vb], (B, cca_hist,
    ``cfg.cca_state_width``). Three paths:

    * ``cache=None``: a whole sequence from position 0, zero state.
    * a cache with ``pages``: the engine's decode round, one token a slot,
      K and V through the page table (:func:`_attend_through_table`: no new
      attention kernel); ``cache['cca']`` is the pool's state leaf, one row a
      slot.
    * a dense cache ``{'k','v','len','cca'}``: one prefill segment appended
      at ``len`` behind a slot's gathered rows.

    On both cached paths ``cache['n_real']`` (B,) says how many of the fed
    rows are real: the returned ``cca`` is the state behind the last real
    row (a padded chunk's at its last real token; a masked lane's, with 0
    real rows, unchanged)."""
    heads, kv, dh = cfg.num_heads, cfg.kv_heads, cfg.dh
    group = heads // kv
    cq, ck = heads * dh, kv * dh
    cvb = (kv // 2) * dh
    n_hist, t0, t1 = cfg.cca_hist, cfg.cca_time0, cfg.cca_time1
    h = block_norm(cfg, "ln1")(x)
    b, s, _ = h.shape
    with jax.named_scope("cca.proj"):
        z = nn.Dense(cq + 2 * ck, dtype=cfg.compute_dtype,
                     use_bias=cfg.use_bias, name="cca_in")(h)
    va = z[..., cq + ck:cq + 2 * ck - cvb]
    cur = jnp.concatenate([z[..., :cq + ck], z[..., cq + 2 * ck - cvb:]], -1)
    hist = (jnp.zeros((b, n_hist, cur.shape[-1]), cur.dtype)
            if cache is None else cache["cca"].astype(cur.dtype))
    full = jnp.concatenate([hist, cur], 1)  # row i is position i - n_hist
    with jax.named_scope("cca.conv"):
        lat = full[..., :cq + ck].astype(jnp.float32)
        w0 = mod.param("cca_conv0", nn.initializers.normal(t0 ** -0.5),
                       (cq + ck, t0)).astype(jnp.float32)
        w1 = mod.param("cca_conv1", nn.initializers.normal((t1 * dh) ** -0.5),
                       (heads + kv, t1, dh, dh)).astype(jnp.float32)
        # a at positions -(t1 - 1) .. s - 1, then b at 0 .. s - 1.
        off, n_a = n_hist - (t0 - 1) - (t1 - 1), s + t1 - 1
        a = sum(lat[:, off + t0 - 1 - j:off + t0 - 1 - j + n_a] * w0[:, j]
                for j in range(t0)).reshape(b, n_a, heads + kv, dh)
        conv = sum(jnp.einsum("bthd,hde->bthe", a[:, t1 - 1 - j:t1 - 1 - j + s],
                              w1[:, j], precision=jax.lax.Precision.HIGHEST)
                   for j in range(t1))
        q_lat = lat[:, n_hist:, :cq].reshape(b, s, kv, group, dh)
        k_lat = lat[:, n_hist:, cq:].reshape(b, s, kv, dh)
        q = conv[:, :, :heads].reshape(b, s, kv, group, dh) + 0.5 * (
            q_lat + k_lat[:, :, :, None])
        k = conv[:, :, heads:] + 0.5 * (q_lat.mean(3) + k_lat)
        temp = mod.param("cca_temp", nn.initializers.ones, (kv,))
        q = _l2_heads(q, dh).reshape(b, s, heads, dh)
        k = _l2_heads(k, dh) * temp.astype(jnp.float32)[:, None]
    if getattr(cfg, "position", "learned") == "rope":
        cos, sin = rope_tables(
            cfg.rope_dims, s, cfg.rope_theta, positions=positions,
            start=0 if cache is None or "pages" in cache else cache["len"])
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    q4, k4 = q.astype(cfg.compute_dtype), k.astype(cfg.compute_dtype)
    v4 = jnp.concatenate(
        [va, full[:, n_hist - 1:n_hist - 1 + s, cq + ck:]], -1
    ).reshape(b, s, kv, dh)
    to_heads = lambda t4: t4.transpose(0, 2, 1, 3)
    if cache is None:
        attn = to_heads(A.dense_attention(
            to_heads(q4), to_heads(jnp.repeat(k4, group, axis=2)),
            to_heads(jnp.repeat(v4, group, axis=2)), causal=True))
        attn = attn.reshape(b, s, cq)
    else:
        with jax.named_scope("cca.state"):
            state = jax.vmap(
                lambda rows, n: jax.lax.dynamic_slice_in_dim(rows, n, n_hist)
            )(full, cache["n_real"]).astype(cache["cca"].dtype)
        if "pages" in cache:
            attn, cache = _attend_through_table(cfg, cache, q4, k4, v4)
        else:
            ks = jax.lax.dynamic_update_slice(
                cache["k"], to_heads(k4), (0, 0, cache["len"], 0))
            vs = jax.lax.dynamic_update_slice(
                cache["v"], to_heads(v4), (0, 0, cache["len"], 0))
            scores = jnp.einsum(
                "bkgqd,bkTd->bkgqT",
                to_heads(q4).reshape(b, kv, group, s, dh), ks,
                preferred_element_type=jnp.float32,
            ) / np.sqrt(dh)
            q_pos = cache["len"] + jnp.arange(s)
            allowed = jnp.arange(ks.shape[2])[None, :] <= q_pos[:, None]
            weights = jax.nn.softmax(
                jnp.where(allowed[None, None, None], scores, A.NEG_INF), -1)
            attn = jnp.einsum(
                "bkgqT,bkTd->bkgqd", weights, vs.astype(jnp.float32)
            ).astype(cfg.compute_dtype)
            attn = to_heads(attn.reshape(b, heads, s, dh)).reshape(b, s, cq)
            cache = dict(cache, k=ks, v=vs, len=cache["len"] + s)
        cache = dict(cache, cca=state)
    attn = nn.Dense(cfg.d_model, dtype=cfg.compute_dtype,
                    use_bias=cfg.use_bias, name="proj")(attn)
    return x + attn, cache


def mla_prefill_form(cfg, queries: int, rows: int) -> str:
    """The form in which ``queries`` rows of latent attention attend a
    gathered row of ``rows`` positions: the one of fewer FLOPs by these
    counts, a function of the shapes and of nothing else. ``"expanded"``
    makes every position's per-head K and V from its latent (2 r H (nope +
    v) a position) and attends at width nope + rope for q.k and v for the
    values (2 H (nope + rope + v) a query a position); ``"absorbed"`` folds
    each query into the latent (2 H r (nope + v) a query, in and out) and
    attends the rows as they are (2 H (2 r + rope) a query a position). At
    DeepSeek-V2's widths over a row of 32768 the absorbed form wins up to
    169 queries: a 128-wide tail over a cached document is absorbed, a
    256-wide one and every whole chunk expanded."""
    h, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    expanded = 2 * rows * r * h * (nope + v) + 2 * queries * rows * h * (
        nope + rope + v)
    absorbed = 2 * queries * h * r * (nope + v) + 2 * queries * rows * h * (
        2 * r + rope)
    return "absorbed" if absorbed < expanded else "expanded"


def mla_prefill_widths(cfg, rows: int, chunk: int) -> tuple[int, ...]:
    """The prefill widths a latent-attention engine compiles below its
    chunk of ``chunk`` over a row of ``rows``: the widest power of two that
    ``mla_prefill_form`` absorbs, then its doublings under the chunk, so
    that a tail over a cached prefix pads to at most twice its width. At
    DeepSeek-V2's widths over 32768 rows with chunks of 1024: 128 (absorbed),
    256 and 512 (expanded)."""
    w = 1
    while w * 2 < chunk and mla_prefill_form(cfg, w * 2, rows) == "absorbed":
        w *= 2
    widths = []
    while w < chunk:
        widths.append(w)
        w *= 2
    return tuple(widths)


def mla_rows_expanded(cfg, offset: int, width: int, rows: int, dtype) -> int:
    """The latent rows a layer expands to K and V for a prefill of ``width``
    queries at ``offset`` over a gathered row of ``rows`` positions of
    ``dtype``: 0 in the absorbed form; in the expanded form those below the
    chunk's end, rounded up to the key block of the kernel
    (``ops.attention.mla_chunk_rows``) or, off its tiles, of the
    ``jax.numpy`` loop (:func:`_mla_attend_rows`)."""
    if mla_prefill_form(cfg, width, rows) == "absorbed":
        return 0
    if A.chunk_flash_fits(dtype, cfg.mla_row, (rows, width)):
        return A.mla_chunk_rows(offset, width, rows, dtype)
    blk = min(rows, 1024)
    return -(-(offset + width) // blk) * blk


def _mla_rope(cfg, positions):
    """cos/sin tables (..., rope/2) of the rope part at ``positions``: YaRN
    where the config has it, plain RoPE elsewhere."""
    rope = cfg.qk_rope_head_dim
    if not cfg.yarn_factor:
        return rope_tables(rope, 0, cfg.rope_theta, positions=positions)
    inv = yarn_inv_freq(rope, cfg.rope_theta, cfg.yarn_factor,
                        cfg.yarn_original_len, cfg.yarn_beta_fast,
                        cfg.yarn_beta_slow)
    scale = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return yarn_tables(positions, inv, scale)


def _mla_pairs_to_halves(x):
    """DeepSeek-V2 lays a rope part out as interleaved pairs (x0, x1), (x2,
    x3), ... and turns them into halves [x0 x2 ... | x1 x3 ...] before its
    rotate-half: the same permutation here, so that the weights are read as
    published."""
    return x.reshape(*x.shape[:-1], -1, 2).swapaxes(-1, -2).reshape(x.shape)


def _mla_attend_rows(cfg, q_nope, q_pe, lat, q_pos, n_rows, w_kb, form):
    """Latent attention of queries ``q_nope`` (B, s, H, nope) and ``q_pe``
    (B, s, H, rope), rotated, over the rows ``lat`` (B, S, row) of
    positions 0 .. S-1 ([c | k_pe | padding]), causally at ``q_pos`` (s,),
    keys below ``n_rows`` (traced) and no further, a block of rows at a
    time with an online softmax in f32: blocks wholly past ``n_rows`` are
    neither read nor computed. ``form`` (:func:`mla_prefill_form`) says
    whether each block's K and V are expanded from its latents by ``w_kb``
    (r, H, nope + v) or the queries are folded into the latent. Returns
    (B, s, H, v) in the compute dtype."""
    b, s, heads, nope = q_nope.shape
    r, rope, vd = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = cfg.compute_dtype
    blk = min(lat.shape[1], 1024)
    if lat.shape[1] % blk:
        lat = jnp.pad(lat, ((0, 0), (0, -lat.shape[1] % blk), (0, 0)))
    w_uk, w_uv = w_kb[..., :nope].astype(dt), w_kb[..., nope:].astype(dt)
    absorbed = form == "absorbed"
    if absorbed:
        with jax.named_scope("mla.absorb"):
            q_main = jnp.einsum("bqhd,chd->bqhc", q_nope.astype(dt), w_uk,
                                preferred_element_type=jnp.float32).astype(dt)
    else:
        q_main = q_nope.astype(dt)
    q_pe = q_pe.astype(dt)
    width = r if absorbed else vd

    def body(i, carry):
        m, l, acc = carry
        rows = jax.lax.dynamic_slice_in_dim(lat, i * blk, blk, axis=1)
        c, k_pe = rows[..., :r], rows[..., r:r + rope]
        if absorbed:
            k_main, vals = c, c
            s_main = jnp.einsum("bqhc,btc->bhqt", q_main, k_main,
                                preferred_element_type=jnp.float32)
        else:
            with jax.named_scope("mla.expand"):
                kv = jnp.einsum("btc,chd->bthd", c, w_kb.astype(dt),
                                preferred_element_type=jnp.float32).astype(dt)
            k_main, vals = kv[..., :nope], kv[..., nope:]
            s_main = jnp.einsum("bqhd,bthd->bhqt", q_main, k_main,
                                preferred_element_type=jnp.float32)
        sc = (s_main + jnp.einsum("bqhd,btd->bhqt", q_pe, k_pe,
                                  preferred_element_type=jnp.float32)
              ) * cfg.mla_scale
        key = i * blk + jnp.arange(blk)
        sc = jnp.where((key[None, :] <= q_pos[:, None])[None, None], sc,
                       A.NEG_INF)
        m_new = jnp.maximum(m, sc.max(-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new)
        l = alpha * l + p.sum(-1, keepdims=True)
        spec = "bhqt,btc->bhqc" if absorbed else "bhqt,bthc->bhqc"
        pv = jnp.einsum(spec, p, vals.astype(jnp.float32))
        return m_new, l, alpha * acc + pv

    carry = (jnp.full((b, heads, s, 1), A.NEG_INF, jnp.float32),
             jnp.zeros((b, heads, s, 1), jnp.float32),
             jnp.zeros((b, heads, s, width), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, (n_rows + blk - 1) // blk, body, carry)
    out = (acc / l).transpose(0, 2, 1, 3)  # (B, s, H, width)
    if absorbed:
        with jax.named_scope("mla.unabsorb"):
            out = jnp.einsum("bqhc,chd->bqhd", out.astype(dt), w_uv,
                             preferred_element_type=jnp.float32)
    return out.astype(dt)


def _mla_flash(cfg, q_nope, q_pe, lat, offset, w_kb, form):
    """:func:`_mla_attend_rows` of one slot's prefill chunk through a chunk
    kernel, for a gathered row ``lat`` (1, S, row) whose shapes the kernels
    take; the chunk's rows start at ``offset``. Absorbed
    (``ops.attention.chunk_flash_attention``): the H queries [q~ | q_pe | 0]
    of a row are one kv head's group over the latent rows as they lie, each
    row its own value (the output's first r lanes are softmax . c).
    Expanded (``ops.attention.mla_chunk_attention``): every head's K and V
    made inside the kernel, from the rows below the chunk's end alone.
    Returns (1, s, H, v) in the compute dtype."""
    _, s, heads, nope = q_nope.shape
    r = cfg.kv_lora_rank
    dt = cfg.compute_dtype
    pad = lambda x, n: jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                               + [(0, n - x.shape[-1])])
    heads_first = lambda x: x.transpose(0, 2, 1, 3)
    if form == "absorbed":
        with jax.named_scope("mla.absorb"):
            q_lat = jnp.einsum("bqhd,chd->bqhc", q_nope.astype(dt),
                               w_kb[..., :nope].astype(dt),
                               preferred_element_type=jnp.float32)
        q = pad(jnp.concatenate([q_lat.astype(dt), q_pe.astype(dt)], -1),
                cfg.mla_row)
        rows = lat[:, None].astype(dt)
        o = A.chunk_flash_attention(heads_first(q), rows, rows, offset,
                                    scale=cfg.mla_scale)
        with jax.named_scope("mla.unabsorb"):
            out = jnp.einsum("bhqc,chd->bqhd", o[..., :r].astype(dt),
                             w_kb[..., nope:].astype(dt),
                             preferred_element_type=jnp.float32)
        return out.astype(dt)
    with jax.named_scope("mla.expand"):
        return A.mla_chunk_attention(
            q_nope.astype(dt), q_pe.astype(dt), lat, w_kb.astype(dt), offset,
            scale=cfg.mla_scale)


def _write_latent_rows(leaf, rows, page, offset, live):
    """Row ``offset[b]`` of page ``page[b]`` of a latent pool leaf (pages,
    1, page_size, row) set to ``rows[b]`` (B, row), for the lanes ``live``
    marks: the page-copy kernel where the leaf fits it (a lane that is not
    live writes nothing), a row scatter elsewhere (the CPU tests' toys; a
    masked lane writes where its page points, the trash page)."""
    if A.paged_decode_fits(leaf):
        return A.paged_row_write(leaf, None, rows[:, None], None, page,
                                 offset, live)
    pages, _, ps, width = leaf.shape
    flat = leaf.reshape(pages * ps, width)
    return flat.at[page * ps + offset].set(rows.astype(leaf.dtype)).reshape(
        leaf.shape)


def mla_attention_sublayer(mod, cfg, x, cache=None, positions=None):
    """Pre-norm multi-head latent attention (DeepSeek-V2, arXiv:2405.04434
    section 2.1; no q-LoRA) + residual, called from ``mod``'s
    ``@nn.compact`` body. With h = norm(x), H heads:

    1. ``q = h W_q``, each head [q_nope | q_pe]; ``[c | k_pe] = h W_kva``,
       ``c <- RMSNorm(c)`` (``kv_norm``); ``[k_nope | v]_h = c W_kvb,h``;
    2. q_pe and k_pe (one a position, every head's) are rotated (YaRN where
       the config has it); logit ``(q_nope . k_nope + q_pe . k_pe) *
       cfg.mla_scale``, causal softmax, ``o_h = softmax . v_h``, ``o W_o``.

    Absorbed, with W_kvb,h = [W_UK,h | W_UV,h]: ``q~_h = q_nope,h W_UK,h^T``
    (r wide), logit ``(q~_h . c + q_pe,h . k_pe) * scale``, ``o_h = (softmax
    . c) W_UV,h``: the same numbers, and the cache is never expanded.

    A position is cached as one row [c | k_pe | zeros] of ``cfg.mla_row``
    lanes (``lat``). Three paths:

    * ``cache=None``: a whole sequence from position 0, expanded.
    * a cache with ``pages``: the engine's decode round, one token a slot;
      the row is written into the pool (:func:`_write_latent_rows`) and the
      absorbed queries, H rows of one slot against its one row a position,
      attend the pages where they lie (``ops.attention.
      paged_decode_attention`` with the row as its own value, ``mla.decode``;
      its sum in ``jax.numpy`` where the pool is off the kernel's tiles).
    * a dense cache ``{'lat', 'len'}``: one prefill chunk appended at ``len``
      behind a slot's gathered rows; it attends in the form
      :func:`mla_prefill_form` gives its shapes, through the chunk kernel
      where the shapes are on its tiles (:func:`_mla_flash`) and in
      ``jax.numpy`` blocks elsewhere (:func:`_mla_attend_rows`).
    """
    heads, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = cfg.compute_dtype
    h = block_norm(cfg, "ln1")(x)
    b, s, d = h.shape
    init = nn.initializers.lecun_normal()
    w_q = mod.param("q_proj", init, (d, heads * (nope + rope)))
    w_kva = mod.param("kv_a", init, (d, r + rope))
    w_kb = mod.param("kv_b", nn.initializers.lecun_normal(
        in_axis=0, out_axis=(1, 2)), (r, heads, nope + vd))
    w_o = mod.param("o_proj", init, (heads * vd, d))
    hd = h.astype(dt)
    q = jnp.einsum("bsd,de->bse", hd, w_q.astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    q = q.reshape(b, s, heads, nope + rope)
    kva = jnp.einsum("bsd,de->bse", hd, w_kva.astype(dt),
                     preferred_element_type=jnp.float32)
    c = RMSNorm(eps=cfg.norm_eps, dtype=dt, name="kv_norm")(kva[..., :r])
    if positions is None:
        start = 0 if cache is None or "pages" in cache else cache["len"]
        positions = (start + jnp.arange(s, dtype=jnp.int32))[None]
    cos, sin = _mla_rope(cfg, positions)  # (B or 1, s, rope/2)
    q_pe = _rotate_heads(_mla_pairs_to_halves(q[..., nope:]), cos, sin)
    k_pe = _rotate_heads(_mla_pairs_to_halves(kva[..., None, r:]).astype(dt),
                         cos, sin)[:, :, 0]
    row = jnp.concatenate(
        [c, k_pe, jnp.zeros((b, s, cfg.mla_row - r - rope), dt)], -1)
    q_nope = q[..., :nope]
    if cache is None:
        attn = _mla_attend_rows(cfg, q_nope, q_pe, row, jnp.arange(s), s,
                                w_kb, "expanded")
    elif "pages" in cache:
        if s != 1:
            raise ValueError(f"a paged cache takes one token per slot, got {s}")
        leaf = _write_latent_rows(
            cache["lat"], row[:, 0], cache["write_page"],
            cache["len"] % cache["lat"].shape[2], cache["attend"] > 0)
        with jax.named_scope("mla.absorb"):
            q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0],
                               w_kb[..., :nope].astype(dt),
                               preferred_element_type=jnp.float32)
            q_row = jnp.concatenate(
                [q_lat.astype(dt), q_pe[:, 0],
                 jnp.zeros((b, heads, cfg.mla_row - r - rope), dt)], -1)
        with jax.named_scope("mla.decode"):
            if A.paged_decode_fits(leaf):
                o_lat = A.paged_decode_attention(
                    q_row[:, None], leaf, None, cache["pages"],
                    cache["attend"], scale=cfg.mla_scale)[:, 0]
            else:
                o_lat = _table_attention_sum(
                    q_row[:, None], leaf, leaf, cache["pages"],
                    cache["attend"], scale=cfg.mla_scale)[:, 0]
        with jax.named_scope("mla.unabsorb"):
            attn = jnp.einsum("bhc,chd->bhd", o_lat[..., :r].astype(dt),
                              w_kb[..., nope:].astype(dt),
                              preferred_element_type=jnp.float32)
        attn = attn.astype(dt)[:, None]
        cache = dict(cache, lat=leaf)
    else:
        lat = jax.lax.dynamic_update_slice(
            cache["lat"], row[:, None].astype(cache["lat"].dtype),
            (0, 0, cache["len"], 0))
        form = mla_prefill_form(cfg, s, lat.shape[2])
        if b == 1 and A.chunk_flash_fits(lat.dtype, cfg.mla_row,
                                         (lat.shape[2], s)):
            attn = _mla_flash(cfg, q_nope, q_pe, lat[:, 0], cache["len"],
                              w_kb, form)
        else:
            attn = _mla_attend_rows(cfg, q_nope, q_pe, lat[:, 0],
                                    cache["len"] + jnp.arange(s),
                                    cache["len"] + s, w_kb, form)
        cache = dict(cache, lat=lat, len=cache["len"] + s)
    out = jnp.einsum("bse,ed->bsd", attn.reshape(b, s, heads * vd),
                     w_o.astype(dt), preferred_element_type=jnp.float32)
    return x + out.astype(x.dtype), cache


def _phase_scope(cached: bool):
    """``jax.named_scope`` on the cached branch (the serving engine's
    programs: op metadata only, the program is unchanged), nothing on the
    training path."""
    return jax.named_scope if cached else (lambda name: contextlib.nullcontext())


class Block(nn.Module):
    cfg: TransformerConfig
    # A leading layer of a config with routed experts whose MLP is the dense
    # one (``cfg.dense_layers``).
    dense_mlp: bool = False

    @nn.compact
    def __call__(self, x, attend, train: bool = False, cache=None,
                 positions=None, self_mask=None, router_state=None):
        """``cache=None`` — training/prefill path. With a cache dict
        ``{'k','v','len'}`` (K/V laid out (B, KV_heads, S_max, dh) —
        num_heads for MHA, num_kv_heads under GQA; ``len`` the filled
        prefix length), runs cached decode and returns
        ``(x, new_cache)``. ``positions`` feeds the RoPE rotation only
        (see :func:`attention_sublayer`); ``self_mask`` is the cached-path
        tree-attention ancestor mask.

        A config with routed experts (``cfg.num_experts``) takes the router
        vector of the layer before as ``router_state`` (None in a stage's
        first layer) and returns its own as one more element, after the
        cache; the cache it returns carries ``moe_counts`` (the tokens each
        held expert received), and a cache's ``route_mask`` (B, S) leaves
        masked lanes and padding out of the routing."""
        cfg = self.cfg
        # The cached (serving) branch names its phases for a profile;
        # the training path's programs stay as they were.
        scope = _phase_scope(cache is not None)
        with scope("attn"):
            if cfg.cca:
                if self_mask is not None:
                    raise CcaUnsupported(
                        "tree attention (self_mask) is not extended to CCA")
                x, cache = cca_attention_sublayer(
                    self, cfg, x, train=train, cache=cache,
                    positions=positions,
                )
            elif cfg.mla:
                if self_mask is not None:
                    raise SlotStateUnsupported(
                        "tree attention (self_mask) is not extended to "
                        "latent attention")
                x, cache = mla_attention_sublayer(
                    self, cfg, x, cache=cache, positions=positions)
            elif cfg.eva:
                if self_mask is not None:
                    raise EvaUnsupported(
                        "tree attention (self_mask) is not extended to EVA")
                x, cache = eva_attention_sublayer(
                    self, cfg, x, train=train, cache=cache,
                    positions=positions,
                )
            else:
                x, cache = attention_sublayer(
                    cfg, x, attend, train=train, cache=cache,
                    positions=positions, self_mask=self_mask,
                )

        if cfg.num_experts and not self.dense_mlp:
            with scope("mlp"):
                # The router reads the norm in float32; the experts take it
                # in compute_dtype.
                y, router_state, counts = routed_experts(
                    self, cfg, _f32_norm(cfg, "ln2")(x), router_state,
                    None if cache is None else cache.get("route_mask"))
                x = x + y.astype(x.dtype)
            if cache is None:
                return x, router_state
            return x, dict(cache, moe_counts=counts), router_state
        with scope("mlp"):
            h = block_norm(cfg, "ln2")(x)
            if cfg.mlp == "swiglu":
                h = nn.silu(matmul_dense(cfg, cfg.d_ff, "mlp_gate")(h)) * (
                    matmul_dense(cfg, cfg.d_ff, "mlp_up")(h))
            else:
                h = matmul_dense(cfg, cfg.d_ff, "mlp_in")(h)
                h = nn.gelu(h)
            h = matmul_dense(cfg, cfg.d_model, "mlp_out")(h)
            if cfg.dropout_rate:
                h = nn.Dropout(cfg.dropout_rate, deterministic=not train)(h)
            x = x + h
        return x if cache is None else (x, cache)


class PatternBlock(nn.Module):
    """One layer of a ``layer_pattern`` config: ONE pre-norm sublayer and
    its residual, of ``kind`` ``M`` (``models/mamba.mamba_mixer``), ``E``
    (``models/moe.routed_experts``) or ``*`` (:func:`attention_sublayer`: the
    attention every other config runs). Returns ``x`` without a cache and
    ``(x, cache)`` with one; the cache is the layer's own leaves (K/V pages or
    a gathered row for ``*``, ``ssm`` / ``conv`` for ``M``, none for ``E``)
    beside what every layer shares, and an ``E`` layer's carries
    ``moe_counts`` back."""

    cfg: TransformerConfig
    kind: str

    @nn.compact
    def __call__(self, x, attend, train: bool = False, cache=None):
        cfg = self.cfg
        scope = _phase_scope(cache is not None)
        if self.kind == "*":
            with scope("attn"):
                out = attention_sublayer(cfg, x, attend, train=train,
                                         cache=cache)
            return out[0] if cache is None else out
        if self.kind == "E":
            with scope("mlp"):
                y, _, counts = routed_experts(
                    self, cfg, _f32_norm(cfg, "ln1")(x), None,
                    None if cache is None else cache.get("route_mask"))
                x = x + y.astype(x.dtype)
            return x if cache is None else (x, dict(cache, moe_counts=counts))
        with scope("ssm"):
            if cache is not None and "ssm" not in cache:
                raise SlotStateUnsupported(
                    "a cache of K/V rows holds no recurrent state: a "
                    "layer_pattern config with 'M' layers is served through "
                    "SlotEngine's pool")
            h = block_norm(cfg, "ln1")(x)
            if cache is None:
                return x + mamba_mixer(self, cfg, h)
            y, state = mamba_mixer(self, cfg, h, cache)
            return x + y, dict(cache, **state)


class TransformerLM(nn.Module):
    """``apply(variables, tokens, positions=None) -> logits`` (f32).

    ``tokens``: (B, S) int32. ``positions``: (B, S) global positions — pass
    them when S is a sequence shard (ring attention); defaults to arange(S).
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, positions=None, train: bool = False, cache=None,
                 self_mask=None, pred_heads: bool = False, logit_rows=None):
        """``pred_heads`` returns every prediction head's logits, (B, S,
        num_pred_heads, vocab), in place of head 0's (B, S, vocab).
        ``logit_rows`` (B,) int32, cached branches only: the head is applied
        to that one row of each sequence and the logits are (B, 1, vocab): a
        prefill chunk's caller reads its last real row and nothing else."""
        cfg = self.cfg
        b, s = tokens.shape
        if cache is not None and positions is None and "pages" in cache:
            # A page pool seen through its tables: every slot of the batch
            # continues at its own length.
            positions = cache["len"][:, None] + jnp.arange(s, dtype=jnp.int32)
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.compute_dtype,
                         name="tok_embed")
        x = embed(tokens)
        if cfg.residual_dtype is not None:
            x = x.astype(cfg.residual_dtype)
        if cfg.position in ("rope", "none"):
            # No position table at all: positions enter as the q/k rotation
            # inside every attention sublayer (ops/rope.py). The blocks
            # receive the caller's global positions (sequence shards) or
            # default to cache-offset arange inside the sublayer. ('none':
            # nor there.)
            pass
        else:
            pos_embed = nn.Embed(
                cfg.max_seq_len, cfg.d_model, dtype=cfg.compute_dtype,
                name="pos_embed",
            )
            if positions is None:
                # Cached decode continues at the filled prefix length; plain
                # forward starts at 0. The lookup runs on the UNBATCHED (s,)
                # positions and broadcasts: every batch row embeds the same
                # positions, and the batched (b, s) gather made the backward
                # a b·s-update scatter-add (1.75 ms/step on the flagship,
                # XPlane r4) where an s-update scatter + the broadcast's
                # reduce does the same job.
                start = cache["len"] if cache is not None else 0
                x = x + pos_embed(start + jnp.arange(s, dtype=jnp.int32))[None]
            else:
                x = x + pos_embed(positions)
        rope_positions = positions if cfg.position == "rope" else None
        attend = _attention_fn(cfg, prefer_packed=cache is None)
        if cache is None:
            if logit_rows is not None:
                raise ValueError("logit_rows is for the cached branches")
            # static_argnums count self at 0: attend (callable) and train
            # (bool) are compile-time constants. Param tree is unchanged —
            # remat is a transform, not a module; positions is a traced
            # (or None) operand, passed by keyword.
            block_cls = (
                nn.remat(Block, static_argnums=(2, 3)) if cfg.remat else Block
            )
            router = None  # routed experts: the layer before's router vector
            for i in range(cfg.num_layers):
                if cfg.layer_pattern:
                    x = PatternBlock(cfg, cfg.layer_pattern[i],
                                     name=f"block_{i}")(x, attend, train)
                    continue
                dense = i < cfg.dense_layers
                x = block_cls(cfg, dense, name=f"block_{i}")(
                    x, attend, train, positions=rope_positions,
                    router_state=router,
                )
                if cfg.num_experts and not dense:
                    x, router = x
        else:
            # Cache layout: {'layers': [{'k','v'}, ...], 'len': scalar} — one
            # shared filled-length for all layers (they advance in lockstep).
            # What else the cache carries beside 'layers' (a page pool's
            # tables) is every layer's too.
            shared = {k_: v_ for k_, v_ in cache.items() if k_ != "layers"}
            new_layers = []
            router, counts = None, []
            for i in range(cfg.num_layers):
                layer = dict(cache["layers"][i], **shared)
                if cfg.layer_pattern:
                    if self_mask is not None:
                        raise SlotStateUnsupported(
                            "tree attention (self_mask) is not extended to a "
                            "layer_pattern config")
                    x, layer = PatternBlock(
                        cfg, cfg.layer_pattern[i], name=f"block_{i}")(
                        x, attend, train=train, cache=layer)
                    if "moe_counts" in layer:
                        counts.append(layer.pop("moe_counts"))
                    new_layers.append(
                        {k_: v_ for k_, v_ in layer.items() if k_ not in shared})
                    continue
                dense = i < cfg.dense_layers
                out = Block(cfg, dense, name=f"block_{i}")(
                    x, attend, train=train, cache=layer,
                    positions=rope_positions, self_mask=self_mask,
                    router_state=router,
                )
                if cfg.num_experts and not dense:
                    x, layer, router = out
                    counts.append(layer.pop("moe_counts"))
                else:
                    x, layer = out
                # Preserve every per-layer buffer (k/v plus the int8
                # cache's k_scale/v_scale); 'len' is shared, not per-layer.
                new_layers.append(
                    {k_: v_ for k_, v_ in layer.items() if k_ not in shared}
                )
            cache = dict(shared, layers=new_layers, len=cache["len"] + s)
            if counts:
                # (layers, held): the tokens each held expert received.
                cache["moe_counts"] = jnp.stack(counts)
            if logit_rows is not None:
                x = jnp.take_along_axis(
                    x, logit_rows.astype(jnp.int32)[:, None, None], axis=1)
                s = 1
        with _phase_scope(cache is not None)("lm_head"):
            x = block_norm(cfg, "ln_f")(x)
            if cfg.tie_embeddings:
                if cfg.num_pred_heads != 1:
                    raise ValueError("a tied head is one vocabulary wide")
                # logits = h E^T over the embedding as it lies, accumulated
                # in float32: no f32 copy of E, no rounding of a logit.
                logits = jnp.einsum(
                    "bsd,vd->bsv", x,
                    embed.embedding.astype(cfg.compute_dtype),
                    preferred_element_type=jnp.float32)
            else:
                logits = nn.Dense(
                    cfg.vocab_size * cfg.num_pred_heads,
                    dtype=jnp.float32 if cfg.fp32_logits else cfg.compute_dtype,
                    name="lm_head", use_bias=cfg.use_bias,
                )(x)
            logits = logits.astype(jnp.float32)
            if pred_heads:
                logits = logits.reshape(
                    b, s, cfg.num_pred_heads, cfg.vocab_size)
            elif cfg.num_pred_heads > 1:
                logits = logits[..., : cfg.vocab_size]  # head 0 is served
        return logits if cache is None else (logits, cache)


def next_token_loss(logits, tokens, weight=None):
    """Mean cross-entropy of logits[:, :-1] predicting tokens[:, 1:].

    ``weight`` (B, S) optionally masks positions (e.g. sequence-shard padding).
    """
    targets = tokens[:, 1:]
    lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]
    if weight is not None:
        w = weight[:, 1:]
        return (nll * w).sum() / jnp.maximum(w.sum(), 1.0)
    return nll.mean()
