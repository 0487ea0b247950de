"""Model architecture config.

Capability parity: realhf/api/core/model_api.py `ReaLModelConfig` (:210-340)
— one config dataclass covering the llama/qwen2/mistral/gemma family plus
MoE and critic variants.
"""

import dataclasses
import functools
from typing import Tuple

import jax.numpy as jnp

# Block leaves of the leading dense layers (`first_k_dense`): a layer's own
# leaf names under this prefix, stacked [first_k_dense, ...].
DENSE_PREFIX = "dense_"
# Block leaves no gradient reaches and no optimizer moves (the sigmoid
# router's choice bias): the train engine keeps no moment for them and the
# hand-back returns them as they were.
FROZEN_LEAVES = (
    "router_bias",
    # the token indexer of a full latent layer (`models/latent_select.py`):
    # its selection carries no gradient and the RL loss has no term for it
    "idx_q", "idx_k", "idx_w", "idx_k_norm", "idx_k_norm_b",
)

# The residual branches x += f(norm(x)) a layer is made of: softmax attention
# over per-head K/V, the same over the last `attn_window` keys alone, latent
# attention over one row a token, Gated DeltaNet, Mamba-2, the gated short
# convolution, a dense MLP, the mixture of experts; softmax attention that
# reads the blocks of keys a query SELECTS (InfLLM-V2) and Lightning linear
# attention, a recurrence with one constant decay a head (minicpm_sala).
ATTENTION, WINDOW, LATENT, GDN, SSM, SCONV, MLP, MOE = (
    "attention", "window", "latent", "gdn", "ssm", "sconv", "mlp", "moe",
)
SPARSE, LIGHTNING = "sparse", "lightning"
# Latent attention by `window_pattern` (dots3_note): a full layer whose
# queries read the `index_topk` latent rows a learned indexer SELECTS, and
# a window layer in a latent geometry of its own over a ring of rows.
LATENT_SELECT, LATENT_WINDOW = "latent_select", "latent_window"
# One character of `layer_pattern` -> that layer's ONE branch.
_PATTERN_KINDS = {"M": (SSM,), "E": (MOE,), "*": (ATTENTION,)}
# One character of `window_pattern` -> that layer's mixer.
_WINDOW_KINDS = {
    "S": WINDOW, "F": ATTENTION, "C": SCONV, "M": SSM, "B": SPARSE,
    "L": LIGHTNING,
}
# The same characters where the plan's attention is latent.
_LATENT_WINDOW_KINDS = {"S": LATENT_WINDOW, "F": LATENT_SELECT}

LayerKind = Tuple[str, ...]  # a layer's branches, in order


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """A model's layers: `prefix`, stepped one by one, then `repeats` scan
    steps of `unit`, a step's layers unrolled.  A leaf of the stored tree
    is stacked over the layers that own it, in layer order — the prefix's
    on their own under `DENSE_PREFIX`."""

    prefix: Tuple[LayerKind, ...]
    unit: Tuple[LayerKind, ...]
    repeats: int

    def in_prefix(self, *branches: str) -> int:
        """Leading layers with one of `branches`."""
        return _layers_with(self.prefix, branches)

    def in_unit(self, *branches: str) -> int:
        return _layers_with(self.unit, branches)

    def count(self, *branches: str) -> int:
        """Layers of the whole model with one of `branches`."""
        return self.in_prefix(*branches) + self.repeats * self.in_unit(*branches)

    @property
    def kinds(self) -> frozenset:
        return frozenset(self.prefix + self.unit)


def _layers_with(layers: Tuple[LayerKind, ...], branches) -> int:
    return sum(any(b in kind for b in branches) for kind in layers)


def _shortest_unit(pattern: str) -> str:
    """The shortest string `pattern` is whole repeats of."""
    for n in range(1, len(pattern) + 1):
        if len(pattern) % n == 0 and pattern[:n] * (len(pattern) // n) == pattern:
            return pattern[:n]
    return pattern


_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "float16": jnp.float16,
}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    n_layers: int
    hidden_dim: int
    n_q_heads: int
    n_kv_heads: int
    head_dim: int
    intermediate_dim: int
    vocab_size: int
    max_position_embeddings: int = 32768
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    qkv_bias: bool = False  # qwen2-style attention bias
    # olmoe: RMSNorm with a learned weight over the WHOLE projected q and k
    # vectors (all heads at once), before the reshape into heads and rope.
    qk_norm: bool = False
    tied_embeddings: bool = False
    is_critic: bool = False
    param_dtype: str = "bfloat16"
    # MoE (0 experts = dense MLP)
    n_experts: int = 0
    n_experts_per_tok: int = 2
    moe_intermediate_dim: int = 0
    # True (mixtral): the top-k router weights are renormalised to sum to
    # one.  False (olmoe `norm_topk_prob: false`): the softmax
    # probabilities over ALL experts are used as they are.
    moe_norm_topk: bool = True
    # Router aux loss coefficient (reference: modules/moe/router.py)
    moe_aux_loss_coef: float = 0.001
    # "grouped" (default): dropless grouped-GEMM over expert-sorted
    # tokens via jax.lax.ragged_dot (megablox-style) — expert FLOPs
    # exactly proportional to tokens, numerics equal to the oracle.
    # "topk": capacity-based dispatch — FLOPs scale with top-k times the
    # capacity factor, tokens over capacity are dropped (GShard-style);
    # the true-EP path (all-to-all over the expert axis).  "dense":
    # every expert computes every token then results are weight-masked —
    # E/k times the FLOPs, kept as the numerics oracle.
    moe_dispatch: str = "grouped"
    # Expert capacity = ceil(T * k / E * this); 1.0 = perfectly balanced.
    moe_capacity_factor: float = 1.25
    # ---- architecture family switches (reference: api/from_hf/*) ----
    hidden_act: str = "silu"  # silu | gelu | gelu_tanh | relu2 (relu(x)^2)
    norm_type: str = "rms"  # rms | layernorm (layernorm adds bias params)
    rms_norm_offset: bool = False  # gemma: scale by (1 + w)
    embed_scale: bool = False  # gemma: embeddings scaled by sqrt(hidden)
    # rope | learned (gpt2 wpe) | none (nemotron_h: attention takes no
    # positions; they reach the model through its Mamba layers)
    pos_emb: str = "rope"
    # False = plain fc/act/proj (gpt2).  In a mixture of experts: every
    # expert and the shared one are down(act(up(x))), two matrices (`wu`,
    # `wd`) where a gated expert has three.
    mlp_gated: bool = True
    proj_bias: bool = False  # biases on attn-out + mlp matmuls (gpt2)
    # Where a residual branch's norm (`ln1` / `ln2`) sits: "input", x +
    # f(norm(x)) (every other family), or "output", x + norm(f(x)) with f
    # fed the raw stream (OLMo 2's block: olmo_hybrid).
    branch_norm: str = "input"
    # ---- hybrid layer pattern (qwen3_next) ----
    # Layer i is softmax attention when (i + 1) % full_attn_interval == 0,
    # else a Gated DeltaNet (linear attention) block: the stack is scanned
    # by PERIODS of `full_attn_interval` layers.  1 = every layer is full
    # attention, a period of one (every other family).
    full_attn_interval: int = 1
    linear_n_k_heads: int = 0
    linear_n_v_heads: int = 0
    linear_k_head_dim: int = 0
    linear_v_head_dim: int = 0
    linear_conv_kernel: int = 4
    # The delta rule's beta = 2 sigmoid(b) instead of sigmoid(b) (FLA's
    # `allow_neg_eigval`, olmo_hybrid): I - beta k k^T then has an
    # eigenvalue in (-1, 1) and not in (0, 1).
    linear_neg_eigval: bool = False
    # Rotary embedding on the first `rotary_dim` of head_dim only (HF
    # `partial_rotary_factor`); 0 = all of head_dim.
    rotary_dim: int = 0
    # `qk_norm` per HEAD (a [head_dim] weight, after the reshape) instead
    # of olmoe's norm over the whole projection.
    qk_norm_per_head: bool = False
    # The query projection also gives a per-head gate: o_proj(attn *
    # sigmoid(gate)).
    attn_gate: bool = False
    # A SwiGLU expert every token goes through, scaled by a sigmoid gate
    # (0 = none).
    shared_expert_dim: int = 0
    # One expert-parallel rank's share: the router scores `n_router_experts`
    # (0 = n_experts) and this program holds experts [expert_offset,
    # expert_offset + n_experts) of them.  Choices that fall elsewhere add
    # nothing here: the layer's output is this rank's PART of the sum.
    n_router_experts: int = 0
    expert_offset: int = 0
    # ---- latent attention (MLA: deepseek_v3, glm4_moe_lite) ----
    # `kv_lora_rank` > 0: keys and values are up-projections of ONE normed
    # latent vector a token (`kv_lora_rank` wide) beside one roped key part
    # all heads share (`qk_rope_head_dim`); the query is low-rank too
    # (`q_lora_rank`), its heads `qk_nope_head_dim` + `qk_rope_head_dim`
    # wide.  The cache keeps the latent row, not per-head K/V.  `head_dim`
    # is the q/k width and has to equal `v_head_dim`: the attention kernels
    # take one width (unequal widths are refused by name).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # The first `first_k_dense` layers have a dense MLP (`intermediate_dim`)
    # where the others have the mixture of experts; they run before the
    # layer scan, their leaves stacked on their own under `dense_*`.
    first_k_dense: int = 0
    # Router scores over all experts: "softmax", or "sigmoid" with a per-
    # layer bias (`router_bias`, not trained) that enters the CHOICE of the
    # top k and not their weights (deepseek_v3 `noaux_tc`), the chosen
    # weights renormalised per `moe_norm_topk` and scaled by
    # `moe_routed_scale`; no auxiliary loss.
    moe_score_func: str = "softmax"
    moe_routed_scale: float = 1.0
    # Random weights only (`init_params`): the standard deviation of the
    # draw of `router_bias`.  0, a bias of zeros, is how the family starts
    # one; a configuration that wants choice-by-score-plus-bias told from
    # weight-by-score on random weights states its own draw.  A checkpoint
    # brings its bias and never reads this.
    router_bias_init_std: float = 0.0
    # What the sigmoid router adds to the chosen scores' sum before it
    # divides by it (`moe_norm_topk`): deepseek_v3's 1e-20, lfm2_moe's 1e-6.
    moe_norm_topk_eps: float = 1e-20
    # False: the shared expert's output is added as it is (no sigmoid gate).
    shared_expert_gated: bool = True
    # ---- a pattern of ONE-BRANCH layers (nemotron_h) ----
    # One character a layer, in order: "M" a Mamba-2 mixer, "E" the mixture
    # of experts, "*" softmax attention.  Every layer is x += f(norm(x))
    # with f that ONE kind (an expert layer has no mixer, a mixer layer no
    # MLP); the stack is scanned by repeats of the pattern's smallest unit
    # (`pattern_unit`), a unit's layers unrolled.  "" = every layer is a
    # mixer and an MLP (every other family).
    layer_pattern: str = ""
    # Mamba-2 (SSD): `ssm_n_heads` heads of `ssm_head_dim` channels, each
    # with a state [head_dim, ssm_state_dim] in fp32; B and C are shared by
    # the heads of a group (`ssm_n_groups`); a depthwise causal conv WITH
    # bias over x | B | C; training and prefill run chunks of `ssm_chunk`.
    ssm_n_heads: int = 0
    ssm_head_dim: int = 0
    ssm_n_groups: int = 1
    ssm_state_dim: int = 0
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    # Random weights only (`init_params`): dt_bias is the inverse softplus
    # of a log-uniform draw on [min, max] floored at `floor`.
    ssm_dt_min: float = 0.001
    ssm_dt_max: float = 0.1
    ssm_dt_floor: float = 0.0001
    # ---- a mixer a layer, by pattern (mellum, lfm2_moe) ----
    # One character a layer, in order: "S" softmax attention in which a
    # token sees the last `attn_window` keys of its sequence, itself
    # included; "F" full causal attention; "C" the gated short convolution
    # (`models/short_conv.py`: no keys, a cache of the row's last
    # `sconv_kernel` - 1 gated inputs); "M" a Mamba-2 mixer (`ssm_*`, as in
    # a one-branch pattern, here with an MLP behind it: granitemoehybrid).
    # Every layer has an MLP (or the experts) behind its mixer; "S" and
    # "F" have the same leaves; "B" block-sparse softmax attention (the
    # `sparse_*` sizes; a full layer's leaves) and "L" Lightning linear
    # attention (`lightning_*`), minicpm_sala's two mixers.  The
    # first `first_k_dense` characters are the leading dense layers'
    # mixers; the stack is scanned by repeats of the smallest unit of the
    # rest.  "" = every attention layer is full.
    window_pattern: str = ""
    attn_window: int = 0
    # Taps of the short convolution's depthwise causal kernel.
    sconv_kernel: int = 3
    # The rotary embedding by kind of layer: a window layer takes plain
    # rope at `window_rope_theta`; a full layer takes YaRN where `rope_yarn_factor`
    # > 0 (inverse frequencies blended between theta's and theta's over
    # `factor`, by how many turns a dimension makes over
    # `rope_yarn_original` positions: `ops/norms.yarn_inv_freq`), its cos
    # and sin scaled by `rope_yarn_attention_factor` (0 = 0.1 ln(factor) +
    # 1, what the published rule gives).
    window_rope_theta: float = 0.0  # 0 = rope_theta
    rope_yarn_factor: float = 0.0
    rope_yarn_original: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_attention_factor: float = 0.0
    # ---- muP-style multipliers (granitemoehybrid); 1.0 / 0.0 = none ----
    # x0 = E[ids] * embedding_multiplier; every residual add is x +
    # residual_multiplier * f(norm(x)); the attention scores are q k^T *
    # attention_multiplier (0.0 = head_dim ** -0.5); the logits are divided
    # by logits_scaling before any softmax, temperature or top-k/p.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # ---- block-sparse attention (InfLLM-V2: minicpm4, minicpm_sala "B") ----
    # A sequence of at least `sparse_dense_len` tokens: a query attends over
    # `sparse_topk` blocks of `sparse_block_size` keys — the sequence's
    # first `sparse_init_blocks`, the `sparse_window` / block_size ending at
    # its own, and the highest by score against COMPRESSED keys (the mean
    # of `sparse_kernel_size` keys every `sparse_kernel_stride`; a block's
    # score the largest, summed over a key head's query heads, of the
    # kernels that overlap it).  Shorter sequences: plain causal attention.
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window: int = 2048
    sparse_dense_len: int = 8192
    # ---- Lightning attention (minicpm_sala "L") ----
    # `lightning_n_heads` heads of `lightning_head_dim`, q and k normed per
    # head and roped (the ONLY positions of a plan whose `pos_emb` is
    # "none"), S_t = lambda_h S_{t-1} + k_t^T v_t in fp32 with lambda_h =
    # exp(-2 ** (-8 (h + 1) / H)), y_t = q_t S_t / sqrt(d), an RMSNorm per
    # head over y and a sigmoid output gate.
    lightning_n_heads: int = 0
    lightning_head_dim: int = 0
    # ---- latent attention in two geometries (dots3_note) ----
    # With `window_pattern` and `kv_lora_rank`: an "F" layer is latent
    # attention at the sizes above (q/k heads `head_dim` = nope + rope wide,
    # v heads `v_head_dim`, which may differ) and an "S" layer latent
    # attention at the `swa_*` sizes over the last `attn_window` keys, its
    # cache a ring of latent rows, its rope `window_rope_theta`.
    # `index_topk` > 0: an "F" layer's query attends over the `index_topk`
    # visible keys of largest index score alone (ties to the lower
    # position): I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s]) over
    # `index_n_heads` heads of `index_head_dim`, qI from the query's latent,
    # kI a LayerNormed projection of the layer's input, both roped on their
    # first `qk_rope_head_dim` columns; the cache keeps kI beside the latent
    # row.  The indexer takes no gradient (`FROZEN_LEAVES`).
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # One sigmoid gate a HEAD on the attention output before `o_proj`, a
    # projection of the layer's normed input (`attn_gate`: one an element).
    attn_gate_headwise: bool = False
    # The normed query and key/value latents are multiplied by sqrt(hidden /
    # rank) (`apply_mla_qkv_lora_rescale`); the shared rope key is not.
    latent_rescale: bool = False
    swa_n_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    # One tensor-parallel rank's share of the HEADS: `n_q_heads` and
    # `swa_n_heads` are the heads HELD, of `head_share` times as many; the
    # low-rank down-projections, their norms and the indexer are whole.  A
    # layer's attention output is this rank's PARTIAL `o_proj` sum (no
    # exchange).
    head_share: int = 1
    # Generation by diffusion over blocks (`engines/block_diffusion.py`):
    # `block_length` B > 0 (0 = autoregressive: every other family) makes
    # attention BLOCK-causal — token i sees token j of its sequence iff
    # floor(pos_j / B) <= floor(pos_i / B) — the head's row at position i
    # the distribution of the token AT i (no shift, the logit of
    # `mask_token_id` left out of every softmax), a decode step a block of
    # B tokens in `denoising_steps` forwards and a commit, and the
    # trainer's input two streams (clean | masked).  A denoising step
    # reveals the B / `denoising_steps` masked places its draw is most
    # confident of (the family's `low_confidence_static`; the one sampler
    # parameter, the model's own: `denoising_forwards`).
    block_length: int = 0
    mask_token_id: int = -1
    denoising_steps: int = 0  # 0 = block_length: one token a forward

    def __post_init__(self):
        self._check_blocks()
        # The checks read the fields as given: `plan` is for what passed.
        if (self.n_layers - self.first_k_dense) % self.full_attn_interval:
            raise ValueError(
                f"{self.n_layers} layers are not whole periods of "
                f"{self.full_attn_interval} (full_attn_interval)"
            )
        if self.first_k_dense and not (
            (self.is_latent or self.window_pattern) and self.is_moe
            and self.first_k_dense < self.n_layers
        ):
            raise NotImplementedError(
                f"first_k_dense {self.first_k_dense}: leading dense layers "
                "come before the scanned sparse layers of a mixture-of-"
                "experts model whose mixers are latent attention or stated "
                "by `window_pattern`"
            )
        if self.layer_pattern:
            self._check_pattern()
        if self.window_pattern:
            self._check_windows()
        if self.rope_yarn_factor and not (
            self.rope_yarn_original and self.pos_emb == "rope"
            and not self.is_latent and not self.rotary_dim
        ):
            raise NotImplementedError(
                "YaRN scales the whole-head rotary embedding of softmax "
                "attention and needs the positions it was trained over "
                "(rope_yarn_original)"
            )
        if self.branch_norm not in ("input", "output"):
            raise ValueError(f"unknown branch_norm {self.branch_norm!r}")
        if self.moe_score_func not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown moe_score_func {self.moe_score_func!r}")
        if self.attention_multiplier and self.is_latent:
            raise NotImplementedError(
                "attention_multiplier is folded into the per-head q of "
                "softmax attention; latent attention's absorbed decode step "
                "takes head_dim ** -0.5"
            )
        if self.is_latent:
            qk = self.qk_nope_head_dim + self.qk_rope_head_dim
            if not (qk == self.head_dim and (
                    self.window_pattern or qk == self.v_head_dim)):
                raise NotImplementedError(
                    f"latent attention with q/k heads {qk} wide and v heads "
                    f"{self.v_head_dim} (head_dim {self.head_dim}): the "
                    "attention kernels take one width for q, k and v (a "
                    "plan by `window_pattern` carries v on zero columns)"
                )
            if self.full_attn_interval > 1 or self.n_kv_heads != self.n_q_heads or not (
                self.q_lora_rank and self.pos_emb == "rope"
            ):
                raise NotImplementedError(
                    "latent attention is one kind of layer with a low-rank "
                    "query, rotary positions and as many key as query heads"
                )
        if self.expert_offset + self.n_experts > self.router_width:
            raise ValueError(
                f"experts [{self.expert_offset}, "
                f"{self.expert_offset + self.n_experts}) lie outside the "
                f"router's {self.router_width} outputs"
            )

    def _check_pattern(self):
        pattern = self.layer_pattern
        if "-" in pattern:
            raise NotImplementedError(
                f"layer_pattern {pattern!r}: the dense MLP layer kind '-' "
                "is not built (a layer is 'M', 'E' or '*')"
            )
        if set(pattern) - set("ME*") or len(pattern) != self.n_layers:
            raise ValueError(
                f"layer_pattern {pattern!r} is not {self.n_layers} "
                "characters of 'M' (Mamba-2), 'E' (experts), '*' (attention)"
            )
        if self.full_attn_interval > 1 or self.is_latent or self.first_k_dense:
            raise NotImplementedError(
                "a pattern of one-branch layers has no Gated DeltaNet "
                "layers, no latent attention and no leading dense layers"
            )
        if "E" in pattern and not self.is_moe:
            raise ValueError("an 'E' layer needs n_experts > 0")
        if "M" in pattern:
            self._check_ssm()

    def _check_ssm(self):
        if (
            not (self.ssm_n_heads and self.ssm_head_dim and self.ssm_state_dim)
            or self.ssm_n_heads % self.ssm_n_groups
            or self.ssm_inner_dim % self.ssm_n_groups
        ):
            raise ValueError(
                f"an 'M' layer needs ssm_n_heads ({self.ssm_n_heads}) x "
                f"ssm_head_dim ({self.ssm_head_dim}) channels in whole "
                f"groups ({self.ssm_n_groups}) and a state "
                f"({self.ssm_state_dim})"
            )

    def _check_windows(self):
        pattern = self.window_pattern
        if set(pattern) - set(_WINDOW_KINDS) or len(pattern) != self.n_layers:
            raise ValueError(
                f"window_pattern {pattern!r} is not {self.n_layers} "
                "characters of 'S' (sliding window), 'F' (full attention), "
                "'C' (gated short convolution), 'M' (Mamba-2), 'B' (block-"
                "sparse attention), 'L' (Lightning attention)"
            )
        if "B" in pattern or "L" in pattern:
            self._check_sala()
        if "S" in pattern and self.attn_window < 1:
            raise ValueError("an 'S' layer needs attn_window >= 1")
        if "C" in pattern and self.sconv_kernel < 2:
            raise ValueError("a 'C' layer needs sconv_kernel >= 2")
        if "M" in pattern:
            self._check_ssm()
            if "M" in pattern[:self.first_k_dense]:
                raise NotImplementedError(
                    f"window_pattern {pattern!r}: a leading dense layer's "
                    "mixer is 'F' or 'C' (a state before the scan was not "
                    "tested)"
                )
        if self.is_latent:
            self._check_latent_windows()
        elif (self.index_topk or self.attn_gate_headwise or self.latent_rescale
              or self.head_share != 1):
            raise NotImplementedError(
                "the token indexer, the headwise gate, the latent rescale "
                "and a share of the heads are latent attention's "
                "(kv_lora_rank > 0) in a plan by `window_pattern`"
            )
        if (
            self.layer_pattern or self.full_attn_interval > 1
            or (self.attn_gate and "B" not in pattern)
        ):
            raise NotImplementedError(
                "a pattern of mixers stands beside plain softmax-attention "
                "layers only: no one-branch pattern, Gated DeltaNet layers "
                "or output gate (but a block-sparse layer's, and latent "
                "attention's headwise one)"
            )
        if "S" in pattern[:self.first_k_dense]:
            raise NotImplementedError(
                f"window_pattern {pattern!r}: a leading dense layer's mixer "
                "is 'F' or 'C' (a ring before the scan was not tested)"
            )

    def _check_latent_windows(self):
        pattern = self.window_pattern
        if set(pattern) - set("SF"):
            raise NotImplementedError(
                f"window_pattern {pattern!r}: latent attention stands in "
                "'F' (full) and 'S' (sliding window) layers alone"
            )
        if "S" in pattern and not (
            self.swa_n_heads and self.swa_q_lora_rank and self.swa_kv_lora_rank
            and self.swa_qk_nope_head_dim and self.swa_v_head_dim
            and self.swa_qk_rope_head_dim == self.qk_rope_head_dim
        ):
            raise NotImplementedError(
                "an 'S' layer of latent attention needs its own geometry "
                "(swa_n_heads, swa_q_lora_rank, swa_kv_lora_rank, "
                "swa_qk_nope_head_dim, swa_v_head_dim) and the full layers' "
                f"rope width ({self.swa_qk_rope_head_dim} against "
                f"{self.qk_rope_head_dim}: one width of rotary table)"
            )
        if self.index_topk and not (
            self.index_n_heads and self.index_head_dim >= self.qk_rope_head_dim
        ):
            raise ValueError(
                "index_topk needs index_n_heads heads of index_head_dim, at "
                "least the rope's width"
            )
        if self.head_share < 1:
            raise ValueError(f"head_share {self.head_share} is below 1")

    def _check_blocks(self):
        b = self.block_length
        if not b:
            return
        if b < 0 or not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                f"block_length {b} needs a mask_token_id inside the "
                f"vocabulary ({self.mask_token_id} of {self.vocab_size})"
            )
        if not 0 <= self.denoising_steps <= b:
            raise ValueError(
                f"denoising_steps {self.denoising_steps} of a block of {b}")
        other = (
            self.window_pattern or self.layer_pattern or self.is_latent
            or self.full_attn_interval > 1 or self.first_k_dense
            or self.is_critic or self.pos_emb != "rope"
        )
        if other:
            raise NotImplementedError(
                f"block_length {b}: generation by diffusion over blocks is "
                "built for full softmax-attention layers with rope alone — "
                "a window or selected layer's band, latent rows, a "
                "recurrent or convolution mixer's state and a value head "
                "have no block-causal form here"
            )

    def _check_sala(self):
        pattern = self.window_pattern
        if set(pattern) - set("BL") or self.first_k_dense or self.is_moe:
            raise NotImplementedError(
                f"window_pattern {pattern!r}: block-sparse ('B') and "
                "Lightning ('L') layers stand beside each other alone, a "
                "dense MLP behind each"
            )
        if "L" in pattern and not (
            self.lightning_n_heads and self.lightning_head_dim == self.head_dim
            and self.qk_norm and self.qk_norm_per_head
        ):
            raise NotImplementedError(
                "an 'L' layer needs lightning_n_heads heads as wide as the "
                f"attention heads ({self.lightning_head_dim} against "
                f"{self.head_dim}: one rotary table) and the per-head q/k "
                "norm"
            )
        ks, st, bs = (self.sparse_kernel_size, self.sparse_kernel_stride,
                      self.sparse_block_size)
        if "B" in pattern and not (
            ks == 2 * st and bs == 4 * st and self.sparse_window % bs == 0
            and self.sparse_init_blocks >= 0
            and self.sparse_topk >= (
                self.sparse_init_blocks + self.sparse_window // bs)
            and self.sparse_dense_len >= ks
        ):
            raise NotImplementedError(
                f"block selection with kernel {ks} / stride {st} / block {bs}"
                f" / window {self.sparse_window} / topk {self.sparse_topk}: "
                "the kernels are two strides, a block four (a max-pool of 5 "
                "by 4), the window whole blocks and the forced blocks within "
                "topk"
            )

    @property
    def dtype(self):
        return _DTYPES[self.param_dtype]

    @functools.cached_property
    def plan(self) -> LayerPlan:
        """The layers as `prefix + unit x repeats`, from the fields that
        state them, in four shapes: `layer_pattern` (one character a
        layer, ONE branch each; the unit is the shortest string the
        pattern repeats); else `window_pattern` (one character a layer's
        MIXER — window or full attention, the gated short convolution — an
        MLP each: `first_k_dense` leading layers with a dense MLP and the
        pattern's first mixers, then repeats of the shortest unit of the
        rest); else periods of `full_attn_interval` - 1 Gated DeltaNet
        layers and one attention layer, a mixer and an MLP each, behind
        `first_k_dense` leading layers with a dense MLP whose mixer is the
        model's (attention or latent attention); else, a period of one,
        every layer that mixer and an MLP."""
        if self.layer_pattern:
            unit = tuple(_PATTERN_KINDS[c] for c in self.pattern_unit)
            return LayerPlan((), unit, len(self.layer_pattern) // len(unit))
        mlp = MOE if self.is_moe else MLP
        if self.window_pattern:
            k = self.first_k_dense
            rest = self.window_pattern[k:]
            kinds = _LATENT_WINDOW_KINDS if self.is_latent else _WINDOW_KINDS
            unit = tuple((kinds[c], mlp) for c in _shortest_unit(rest))
            return LayerPlan(
                prefix=tuple(
                    (kinds[c], MLP) for c in self.window_pattern[:k]
                ),
                unit=unit,
                repeats=len(rest) // len(unit),
            )
        mixer = LATENT if self.is_latent else ATTENTION
        n = self.full_attn_interval
        return LayerPlan(
            prefix=((mixer, MLP),) * self.first_k_dense,
            unit=((GDN, mlp),) * (n - 1) + ((mixer, mlp),),
            repeats=(self.n_layers - self.first_k_dense) // n,
        )

    @property
    def is_pattern(self) -> bool:
        """Whether every layer is ONE branch."""
        return all(len(kind) == 1 for kind in self.plan.kinds)

    @property
    def pattern_unit(self) -> str:
        """The shortest string the pattern is whole repeats of: what one
        step of the layer scan unrolls."""
        return _shortest_unit(self.layer_pattern)

    @property
    def n_window_layers(self) -> int:
        """Layers whose cache is a ring of `attn_window` slots."""
        return self.plan.count(WINDOW, LATENT_WINDOW)

    @property
    def n_ssm_layers(self) -> int:
        return self.plan.count(SSM)

    @property
    def n_sparse_layers(self) -> int:
        """Layers that keep k/v AND compressed keys (block selection)."""
        return self.plan.count(SPARSE)

    @property
    def n_lightning_layers(self) -> int:
        return self.plan.count(LIGHTNING)

    @property
    def lightning_dim(self) -> int:
        return self.lightning_n_heads * self.lightning_head_dim

    @property
    def n_sconv_layers(self) -> int:
        """Layers whose cache is the short convolution's last inputs."""
        return self.plan.count(SCONV)

    @property
    def n_moe_layers(self) -> int:
        """Layers with the mixture of experts."""
        return self.plan.count(MOE)

    @property
    def n_attn_layers(self) -> int:
        """Layers that keep k/v (or latent rows) for every slot of the
        cache: a window layer keeps a ring (`n_window_layers`)."""
        return self.plan.count(ATTENTION, LATENT, LATENT_SELECT)

    @property
    def has_recurrent_state(self) -> bool:
        """Whether some layer carries a state from token to token (Gated
        DeltaNet, Mamba-2): no split over `model`, `seq` or `pipe` yet; on
        the serving plane a slot beside the page pool for Mamba-2 in
        two-branch layers alone (`transformer.plan_refusal`).  Lightning
        attention's constant-decay state counts."""
        return self.plan.count(GDN, SSM, LIGHTNING) > 0

    @property
    def attn_scale(self) -> float:
        """What q k^T is multiplied by before the softmax."""
        return self.attention_multiplier or self.head_dim**-0.5

    @property
    def ssm_inner_dim(self) -> int:
        return self.ssm_n_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the Mamba conv runs over: x, B and C."""
        return self.ssm_inner_dim + 2 * self.ssm_n_groups * self.ssm_state_dim

    @property
    def ssm_in_dim(self) -> int:
        """in_proj's outputs: z | x B C | dt."""
        return self.ssm_inner_dim + self.ssm_conv_dim + self.ssm_n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_hybrid(self) -> bool:
        return self.plan.count(GDN) > 0

    @property
    def is_latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """Values a token's row of the latent cache holds."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def n_scan_layers(self) -> int:
        """Layers the layer scan runs over: all but the leading dense."""
        return self.plan.repeats * len(self.plan.unit)

    @property
    def n_periods(self) -> int:
        """Steps of the layer scan."""
        return self.plan.repeats

    @property
    def n_linear_layers(self) -> int:
        return self.plan.count(GDN)

    @property
    def linear_key_dim(self) -> int:
        return self.linear_n_k_heads * self.linear_k_head_dim

    @property
    def linear_value_dim(self) -> int:
        return self.linear_n_v_heads * self.linear_v_head_dim

    @property
    def linear_conv_dim(self) -> int:
        """Channels the causal conv runs over: q, k and v."""
        return 2 * self.linear_key_dim + self.linear_value_dim

    @property
    def router_width(self) -> int:
        return self.n_router_experts or self.n_experts

    @property
    def denoising_forwards(self) -> int:
        """T: the denoising forwards a block of `block_length` tokens takes
        (the generator's loop, its counters and every FLOP count read it
        here)."""
        return self.denoising_steps or self.block_length

    @property
    def expert_share(self) -> bool:
        """Whether this program holds only some of the routed experts."""
        return self.router_width != self.n_experts

    @property
    def q_dim(self) -> int:
        return self.n_q_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def as_critic(self) -> "ModelConfig":
        return dataclasses.replace(self, is_critic=True, tied_embeddings=False)


def tiny_config(
    vocab_size: int = 512,
    is_critic: bool = False,
    n_experts: int = 0,
    param_dtype: str = "float32",
) -> ModelConfig:
    """8-layer/64-hidden test model (mirrors the reference's tiny test
    constants, realhf/base/testing.py:36-44)."""
    return ModelConfig(
        n_layers=4,
        hidden_dim=64,
        n_q_heads=4,
        n_kv_heads=2,
        head_dim=16,
        intermediate_dim=128,
        vocab_size=vocab_size,
        max_position_embeddings=1024,
        qkv_bias=True,
        is_critic=is_critic,
        param_dtype=param_dtype,
        n_experts=n_experts,
        moe_intermediate_dim=64 if n_experts else 0,
    )


# Published architecture presets (values from the public model cards).
def qwen2_config(size: str, param_dtype: str = "bfloat16") -> ModelConfig:
    presets = {
        # "1.5b" holds Qwen2.5-Math-1.5B's public numbers (tied head, rope
        # 10,000) — the base of R1-Distill-Qwen-1.5B, whose own public
        # config is NOT tied (a separate 233 M head).  "7b"/"32b" are the
        # R1-Distill-Qwen sizes (qwen2 architecture, untied).
        "1.5b": dict(
            n_layers=28, hidden_dim=1536, n_q_heads=12, n_kv_heads=2,
            head_dim=128, intermediate_dim=8960, vocab_size=151936,
            rope_theta=10000.0, tied_embeddings=True,
        ),
        "7b": dict(
            n_layers=28, hidden_dim=3584, n_q_heads=28, n_kv_heads=4,
            head_dim=128, intermediate_dim=18944, vocab_size=152064,
            rope_theta=10000.0,
        ),
        "32b": dict(
            n_layers=64, hidden_dim=5120, n_q_heads=40, n_kv_heads=8,
            head_dim=128, intermediate_dim=27648, vocab_size=152064,
            rope_theta=1000000.0,
        ),
    }
    return ModelConfig(
        qkv_bias=True,
        rms_norm_eps=1e-6,
        max_position_embeddings=131072,
        param_dtype=param_dtype,
        **presets[size.lower()],
    )


def llama_config(size: str, param_dtype: str = "bfloat16") -> ModelConfig:
    presets = {
        "7b": dict(
            n_layers=32, hidden_dim=4096, n_q_heads=32, n_kv_heads=32,
            head_dim=128, intermediate_dim=11008, vocab_size=32000,
        ),
        "8b": dict(
            n_layers=32, hidden_dim=4096, n_q_heads=32, n_kv_heads=8,
            head_dim=128, intermediate_dim=14336, vocab_size=128256,
            rope_theta=500000.0,
        ),
    }
    return ModelConfig(
        qkv_bias=False,
        rms_norm_eps=1e-5,
        max_position_embeddings=8192,
        param_dtype=param_dtype,
        **presets[size.lower()],
    )
