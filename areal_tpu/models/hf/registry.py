"""HuggingFace checkpoint conversion registry.

Capability parity: realhf/api/from_hf/* + realhf/impl/model/conversion/
hf_registry.py — config⇄config and state-dict⇄state-dict converters per model
family, used for loading pretrained checkpoints and saving HF-format outputs
(so downstream eval harnesses can consume them directly).

Families here (full reference parity, api/from_hf/*): llama, qwen2
(identical tensor naming; qwen2 adds qkv bias), mistral, gemma (gelu_tanh +
(1+w) rms offset + scaled embeddings), mixtral (MoE expert stacking), gpt2
(learned positions, LayerNorm+bias, fused c_attn, non-gated gelu MLP), olmoe
(64-expert MoE under `mlp.experts.{e}`, QK-norm over the whole projection,
top-k router weights not renormalised), qwen3_next (a hybrid period of
Gated DeltaNet and gated attention, a gated shared expert), olmo_hybrid
(the same period with a dense MLP, the norms on a branch's output, the
delta rule's beta in (0, 2), attention without positions), glm4_moe_lite
(the deepseek_v3 block: latent attention, a sigmoid router with an
untrained choice bias, an ungated shared expert, leading dense layers),
nemotron_h (a pattern of one-branch layers: Mamba-2 mixers, ungated relu²
experts behind the sigmoid router, attention without positions), lfm2_moe
(gated short-convolution layers beside attention layers with per-head q/k
norms, leading dense layers, the sigmoid router with its choice bias).
"""

import dataclasses
import json
import os
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from areal_tpu.base import logging
from areal_tpu.models.config import DENSE_PREFIX, ModelConfig

logger = logging.getLogger("hf_registry")


class HFFamily:
    def __init__(
        self,
        name: str,
        config_from_hf: Callable[[dict], ModelConfig],
        config_to_hf: Callable[[ModelConfig], dict],
        # State-dict converters; default = the llama-like tensor naming
        # shared by llama/qwen2/mistral/gemma.
        params_from_sd: Optional[Callable] = None,
        params_to_sd: Optional[Callable] = None,
    ):
        self.name = name
        self.config_from_hf = config_from_hf
        self.config_to_hf = config_to_hf
        # None -> resolved to the llama-like default at use (the functions
        # are defined below the early family registrations).
        self._params_from_sd = params_from_sd
        self._params_to_sd = params_to_sd

    def params_from_sd(self, cfg, sd, dtype=None):
        fn = self._params_from_sd or params_from_hf_state_dict
        return fn(cfg, sd, dtype=dtype)

    def params_to_sd(self, cfg, params):
        fn = self._params_to_sd or params_to_hf_state_dict
        return fn(cfg, params)


HF_FAMILIES: Dict[str, HFFamily] = {}


def register_hf_family(family: HFFamily) -> None:
    HF_FAMILIES[family.name] = family


# ---------------- llama / qwen2 ----------------


def _llama_like_config_from_hf(hf: dict) -> ModelConfig:
    head_dim = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return ModelConfig(
        n_layers=hf["num_hidden_layers"],
        hidden_dim=hf["hidden_size"],
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=head_dim,
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 32768),
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        qkv_bias=hf["model_type"] == "qwen2",
        tied_embeddings=hf.get("tie_word_embeddings", False),
    )


def _llama_like_config_to_hf(cfg: ModelConfig, model_type: str) -> dict:
    return {
        "model_type": model_type,
        "num_hidden_layers": cfg.n_layers,
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "intermediate_size": cfg.intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_position_embeddings,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tied_embeddings,
        "torch_dtype": "bfloat16",
        "architectures": [
            "LlamaForCausalLM" if model_type == "llama" else "Qwen2ForCausalLM"
        ],
    }


register_hf_family(
    HFFamily(
        "llama",
        _llama_like_config_from_hf,
        lambda cfg: _llama_like_config_to_hf(cfg, "llama"),
    )
)
register_hf_family(
    HFFamily(
        "qwen2",
        _llama_like_config_from_hf,
        lambda cfg: _llama_like_config_to_hf(cfg, "qwen2"),
    )
)


# ---------------- state dict conversion (llama-like naming) ----------------


def params_from_hf_state_dict(
    cfg: ModelConfig, sd: Dict[str, np.ndarray], dtype=None,
    skip_mlp: bool = False,
) -> Dict[str, Any]:
    """HF tensors -> layer-stacked pytree.  HF linears are [out, in]; ours
    are [in, out], so weights transpose."""
    import jax.numpy as jnp

    dtype = dtype or cfg.dtype

    def get(name):
        if name not in sd:
            raise KeyError(f"missing tensor {name!r} in checkpoint")
        return np.asarray(sd[name])

    def stack(fmt, transpose=False):
        ts = [get(fmt.format(i)) for i in range(cfg.n_layers)]
        arr = np.stack(
            [t.T if transpose else t for t in ts], axis=0
        )
        return jnp.asarray(arr, dtype=dtype)

    blocks = {
        "ln1": stack("model.layers.{}.input_layernorm.weight"),
        "wq": stack("model.layers.{}.self_attn.q_proj.weight", transpose=True),
        "wk": stack("model.layers.{}.self_attn.k_proj.weight", transpose=True),
        "wv": stack("model.layers.{}.self_attn.v_proj.weight", transpose=True),
        "wo": stack("model.layers.{}.self_attn.o_proj.weight", transpose=True),
        "ln2": stack("model.layers.{}.post_attention_layernorm.weight"),
    }
    if not skip_mlp:  # mixtral routes its MoE tensors separately
        blocks["wg"] = stack("model.layers.{}.mlp.gate_proj.weight", transpose=True)
        blocks["wu"] = stack("model.layers.{}.mlp.up_proj.weight", transpose=True)
        blocks["wd"] = stack("model.layers.{}.mlp.down_proj.weight", transpose=True)
    if cfg.qkv_bias:
        blocks["bq"] = stack("model.layers.{}.self_attn.q_proj.bias")
        blocks["bk"] = stack("model.layers.{}.self_attn.k_proj.bias")
        blocks["bv"] = stack("model.layers.{}.self_attn.v_proj.bias")
    import jax.numpy as jnp

    params = {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), dtype=dtype),
        "blocks": blocks,
        "final_ln": jnp.asarray(get("model.norm.weight"), dtype=dtype),
    }
    if cfg.is_critic:
        if "value_head.weight" in sd:
            # Our own critic checkpoints carry the trained head.
            params["value_head"] = jnp.asarray(
                get("value_head.weight"), dtype=dtype
            )
        else:
            # Critic-from-actor init: fresh value head (reference:
            # conversion/hf_registry.py critic init path).
            params["value_head"] = jnp.zeros((cfg.hidden_dim, 1), dtype=dtype)
    elif not cfg.tied_embeddings:
        params["lm_head"] = jnp.asarray(get("lm_head.weight").T, dtype=dtype)
    return params


def params_to_hf_state_dict(
    cfg: ModelConfig, params: Dict[str, Any], skip_mlp: bool = False
) -> Dict[str, np.ndarray]:
    from areal_tpu.base.distributed import to_host

    out: Dict[str, np.ndarray] = {}
    out["model.embed_tokens.weight"] = to_host(params["embed"]).astype(
        np.float32, copy=False
    )
    out["model.norm.weight"] = to_host(params["final_ln"]).astype(
        np.float32, copy=False
    )
    if cfg.is_critic:
        # Not an HF key — preserved so our critic checkpoints roundtrip
        # (recover would otherwise zero the trained value head).
        out["value_head.weight"] = to_host(params["value_head"]).astype(
            np.float32, copy=False
        )
    elif not cfg.tied_embeddings:
        # ascontiguousarray: safetensors serializes the raw buffer, so a
        # transposed VIEW would be written in untransposed memory order.
        out["lm_head.weight"] = np.ascontiguousarray(
            to_host(params["lm_head"]).astype(np.float32, copy=False).T
        )
    blocks = params["blocks"]

    def unstack(name, arr, transpose=False):
        arr = to_host(arr).astype(np.float32, copy=False)
        for i in range(cfg.n_layers):
            t = arr[i]
            # ascontiguousarray: see lm_head note — safetensors writes the
            # raw buffer and would silently drop the transpose.
            out[name.format(i)] = (
                np.ascontiguousarray(t.T) if transpose else t
            )

    unstack("model.layers.{}.input_layernorm.weight", blocks["ln1"])
    unstack("model.layers.{}.self_attn.q_proj.weight", blocks["wq"], True)
    unstack("model.layers.{}.self_attn.k_proj.weight", blocks["wk"], True)
    unstack("model.layers.{}.self_attn.v_proj.weight", blocks["wv"], True)
    unstack("model.layers.{}.self_attn.o_proj.weight", blocks["wo"], True)
    unstack("model.layers.{}.post_attention_layernorm.weight", blocks["ln2"])
    if not skip_mlp:  # mixtral writes its MoE tensors separately
        unstack("model.layers.{}.mlp.gate_proj.weight", blocks["wg"], True)
        unstack("model.layers.{}.mlp.up_proj.weight", blocks["wu"], True)
        unstack("model.layers.{}.mlp.down_proj.weight", blocks["wd"], True)
    if cfg.qkv_bias:
        unstack("model.layers.{}.self_attn.q_proj.bias", blocks["bq"])
        unstack("model.layers.{}.self_attn.k_proj.bias", blocks["bk"])
        unstack("model.layers.{}.self_attn.v_proj.bias", blocks["bv"])
    return out


# ---------------- mistral ----------------
# Llama tensor naming.  A non-null `sliding_window` makes EVERY layer a
# sliding-window layer (`window_pattern` all "S": a token sees its last
# `sliding_window` keys, the ring cache of the static decode program); null
# is full causal attention.


def _mistral_config_from_hf(hf: dict) -> ModelConfig:
    cfg = dataclasses.replace(_llama_like_config_from_hf(hf), qkv_bias=False)
    window = hf.get("sliding_window")
    if not window:
        return cfg
    return dataclasses.replace(
        cfg, window_pattern="S" * cfg.n_layers, attn_window=int(window)
    )


register_hf_family(
    HFFamily(
        "mistral",
        _mistral_config_from_hf,
        lambda cfg: {
            **_llama_like_config_to_hf(cfg, "mistral"),
            "model_type": "mistral",
            "architectures": ["MistralForCausalLM"],
            "sliding_window": _mistral_window(cfg),
        },
    )
)


def _mistral_window(cfg: ModelConfig):
    """`sliding_window` of a config the mistral family can state: every
    layer's window or null, never a mix of kinds."""
    if "F" in cfg.window_pattern:
        raise NotImplementedError(
            f"window_pattern {cfg.window_pattern!r}: the mistral family has "
            "one `sliding_window` for all layers (mellum has `layer_types`)"
        )
    return cfg.attn_window if cfg.window_pattern else None


# ---------------- gemma ----------------


def _gemma_config_from_hf(hf: dict) -> ModelConfig:
    return ModelConfig(
        n_layers=hf["num_hidden_layers"],
        hidden_dim=hf["hidden_size"],
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=hf["head_dim"],
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 8192),
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        tied_embeddings=True,  # gemma always ties
        hidden_act="gelu_tanh",  # gelu_pytorch_tanh
        rms_norm_offset=True,  # norm scales by (1 + w)
        embed_scale=True,  # embeddings scaled by sqrt(hidden)
    )


def _gemma_config_to_hf(cfg: ModelConfig) -> dict:
    return {
        "model_type": "gemma",
        "num_hidden_layers": cfg.n_layers,
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "intermediate_size": cfg.intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_position_embeddings,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": True,
        "hidden_act": "gelu_pytorch_tanh",
        "hidden_activation": "gelu_pytorch_tanh",
        "torch_dtype": "bfloat16",
        "architectures": ["GemmaForCausalLM"],
    }


register_hf_family(
    HFFamily("gemma", _gemma_config_from_hf, _gemma_config_to_hf)
)


# ---------------- mixtral ----------------


def _mixtral_config_from_hf(hf: dict) -> ModelConfig:
    base = _llama_like_config_from_hf(hf)
    return dataclasses.replace(
        base,
        qkv_bias=False,
        n_experts=hf["num_local_experts"],
        n_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_dim=hf["intermediate_size"],
    )


def _mixtral_config_to_hf(cfg: ModelConfig) -> dict:
    out = _llama_like_config_to_hf(cfg, "mixtral")
    out.update(
        model_type="mixtral",
        architectures=["MixtralForCausalLM"],
        num_local_experts=cfg.n_experts,
        num_experts_per_tok=cfg.n_experts_per_tok,
        intermediate_size=cfg.moe_intermediate_dim or cfg.intermediate_dim,
    )
    return out


def _mixtral_params_from_sd(cfg, sd, dtype=None):
    """Attention/norms via the llama-like path; MoE tensors
    (block_sparse_moe.gate + experts.{e}.w1/w2/w3) stacked over (L, E)."""
    import jax.numpy as jnp

    params = params_from_hf_state_dict(cfg, sd, dtype=dtype, skip_mlp=True)
    dtype = dtype or cfg.dtype

    def stack_experts(fmt, transpose):
        layers = []
        for i in range(cfg.n_layers):
            experts = [
                np.asarray(sd[fmt.format(i, e)])
                for e in range(cfg.n_experts)
            ]
            layers.append(
                np.stack([t.T if transpose else t for t in experts], axis=0)
            )
        return jnp.asarray(np.stack(layers, axis=0), dtype=dtype)

    blocks = params["blocks"]
    blocks["router"] = jnp.asarray(
        np.stack(
            [
                np.asarray(
                    sd[f"model.layers.{i}.block_sparse_moe.gate.weight"]
                ).T
                for i in range(cfg.n_layers)
            ],
            axis=0,
        ),
        dtype=dtype,
    )
    moe = "model.layers.{}.block_sparse_moe.experts.{}"
    blocks["wg"] = stack_experts(moe + ".w1.weight", True)  # [L,E,D,F]
    blocks["wd"] = stack_experts(moe + ".w2.weight", True)  # [L,E,F,D]
    blocks["wu"] = stack_experts(moe + ".w3.weight", True)  # [L,E,D,F]
    return params


def _mixtral_params_to_sd(cfg, params):
    from areal_tpu.base.distributed import to_host

    out = params_to_hf_state_dict(cfg, params, skip_mlp=True)
    blocks = params["blocks"]
    router = to_host(blocks["router"]).astype(np.float32, copy=False)
    wg = to_host(blocks["wg"]).astype(np.float32, copy=False)
    wu = to_host(blocks["wu"]).astype(np.float32, copy=False)
    wd = to_host(blocks["wd"]).astype(np.float32, copy=False)
    moe = "model.layers.{}.block_sparse_moe"
    for i in range(cfg.n_layers):
        out[moe.format(i) + ".gate.weight"] = np.ascontiguousarray(
            router[i].T
        )
        for e in range(cfg.n_experts):
            pre = moe.format(i) + f".experts.{e}"
            out[pre + ".w1.weight"] = np.ascontiguousarray(wg[i, e].T)
            out[pre + ".w2.weight"] = np.ascontiguousarray(wd[i, e].T)
            out[pre + ".w3.weight"] = np.ascontiguousarray(wu[i, e].T)
    return out


register_hf_family(
    HFFamily(
        "mixtral",
        _mixtral_config_from_hf,
        _mixtral_config_to_hf,
        params_from_sd=_mixtral_params_from_sd,
        params_to_sd=_mixtral_params_to_sd,
    )
)


# ---------------- olmoe ----------------
# allenai/OLMoE-1B-7B: llama-like attention without bias plus an RMSNorm
# over the whole projected q and k (`self_attn.q_norm` / `k_norm`); every
# layer's MLP is a MoE under `mlp.gate` (router) and `mlp.experts.{e}`
# (SwiGLU, `gate_proj` / `up_proj` / `down_proj`).  `intermediate_size` is
# the width of ONE expert; there is no dense MLP and no shared expert.


def _olmoe_config_from_hf(hf: dict) -> ModelConfig:
    if hf.get("clip_qkv") is not None:
        raise NotImplementedError(
            f"olmoe clip_qkv={hf['clip_qkv']!r}: clamping q/k/v is not "
            "implemented, and ignoring it would run another model"
        )
    if hf.get("attention_bias", False):
        raise NotImplementedError("olmoe attention_bias=true is not modeled")
    base = _llama_like_config_from_hf(hf)
    return dataclasses.replace(
        base,
        qkv_bias=False,
        qk_norm=True,
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        n_experts=hf["num_experts"],
        n_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_dim=hf["intermediate_size"],
        moe_norm_topk=bool(hf.get("norm_topk_prob", False)),
        moe_aux_loss_coef=hf.get("router_aux_loss_coef", 0.01),
    )


def _olmoe_config_to_hf(cfg: ModelConfig) -> dict:
    out = _llama_like_config_to_hf(cfg, "olmoe")
    out.pop("head_dim")  # not an olmoe key: hidden_size / heads
    out.update(
        architectures=["OlmoeForCausalLM"],
        hidden_act=cfg.hidden_act,
        attention_bias=False,
        clip_qkv=None,
        num_experts=cfg.n_experts,
        num_experts_per_tok=cfg.n_experts_per_tok,
        intermediate_size=cfg.moe_intermediate_dim or cfg.intermediate_dim,
        norm_topk_prob=cfg.moe_norm_topk,
        router_aux_loss_coef=cfg.moe_aux_loss_coef,
    )
    return out


_OLMOE_MLP = "model.layers.{}.mlp"
_OLMOE_EXPERT_LEAVES = (  # ours [L, E, in, out] <- HF [out, in]
    ("wg", "gate_proj"), ("wu", "up_proj"), ("wd", "down_proj"),
)
_OLMOE_NORM_LEAVES = ("q_norm", "k_norm")  # same name on both sides


def _olmoe_params_from_sd(cfg, sd, dtype=None):
    import jax.numpy as jnp

    params = params_from_hf_state_dict(cfg, sd, dtype=dtype, skip_mlp=True)
    dtype = dtype or cfg.dtype
    blocks = params["blocks"]
    layers = range(cfg.n_layers)
    for leaf in _OLMOE_NORM_LEAVES:
        fmt = "model.layers.{}.self_attn." + leaf + ".weight"
        blocks[leaf] = jnp.asarray(
            np.stack([np.asarray(sd[fmt.format(i)]) for i in layers]), dtype
        )
    blocks["router"] = jnp.asarray(
        np.stack(
            [
                np.asarray(sd[_OLMOE_MLP.format(i) + ".gate.weight"]).T
                for i in layers
            ]
        ),
        dtype,
    )
    for leaf, name in _OLMOE_EXPERT_LEAVES:
        fmt = _OLMOE_MLP + ".experts.{}." + name + ".weight"
        blocks[leaf] = jnp.asarray(
            np.stack(
                [
                    np.stack(
                        [
                            np.asarray(sd[fmt.format(i, e)]).T
                            for e in range(cfg.n_experts)
                        ]
                    )
                    for i in layers
                ]
            ),
            dtype,
        )
    return params


def _olmoe_params_to_sd(cfg, params):
    from areal_tpu.base.distributed import to_host

    out = params_to_hf_state_dict(cfg, params, skip_mlp=True)
    host = {
        k: to_host(params["blocks"][k]).astype(np.float32, copy=False)
        for k in (*_OLMOE_NORM_LEAVES, "router", "wg", "wu", "wd")
    }
    for i in range(cfg.n_layers):
        for leaf in _OLMOE_NORM_LEAVES:
            out[f"model.layers.{i}.self_attn.{leaf}.weight"] = host[leaf][i]
        mlp = _OLMOE_MLP.format(i)
        out[mlp + ".gate.weight"] = np.ascontiguousarray(host["router"][i].T)
        for leaf, name in _OLMOE_EXPERT_LEAVES:
            for e in range(cfg.n_experts):
                out[f"{mlp}.experts.{e}.{name}.weight"] = (
                    np.ascontiguousarray(host[leaf][i, e].T)
                )
    return out


register_hf_family(
    HFFamily(
        "olmoe",
        _olmoe_config_from_hf,
        _olmoe_config_to_hf,
        params_from_sd=_olmoe_params_from_sd,
        params_to_sd=_olmoe_params_to_sd,
    )
)


# ---------------- mellum ----------------
# JetBrains/Mellum2: the Qwen3-MoE block (per-head q/k RMSNorm, a softmax
# router over `num_experts` with the top-k weights renormalised, SwiGLU
# experts, no shared expert, no biases; olmoe's tensor names) with
# `layer_types` mixing "sliding_attention" layers (a token sees its last
# `sliding_window` keys; plain rope) and "full_attention" layers (causal;
# YaRN), each kind's rotary parameters under `rope_parameters`.  Every
# layer is sparse (`mlp_layer_types`); `intermediate_size` sizes nothing.
# A `share` group cuts the model to one expert-parallel rank, as
# qwen3_next's does.

_MELLUM_LAYER_TYPES = {"sliding_attention": "S", "full_attention": "F"}


def _mellum_config_from_hf(hf: dict) -> ModelConfig:
    for key, fine in (("attention_bias", False), ("hidden_act", "silu")):
        if hf.get(key, fine) != fine:
            raise NotImplementedError(f"mellum {key}={hf[key]!r} is not modelled")
    n_layers = hf["num_hidden_layers"]
    types = hf["layer_types"]
    if len(types) != n_layers or set(types) - set(_MELLUM_LAYER_TYPES):
        raise ValueError(
            f"mellum layer_types {types!r}: {n_layers} of "
            f"{sorted(_MELLUM_LAYER_TYPES)} are wanted"
        )
    sparse = ["sparse"] * n_layers
    if hf.get("mlp_layer_types", sparse) != sparse:
        raise NotImplementedError(
            f"mellum mlp_layer_types {hf['mlp_layer_types']!r}: a dense MLP "
            "layer among the sparse ones is not built"
        )
    pattern = "".join(_MELLUM_LAYER_TYPES[t] for t in types)
    if "S" in pattern and not hf.get("use_sliding_window", True):
        raise NotImplementedError(
            "mellum use_sliding_window=false with sliding_attention layers: "
            "which of the two holds is not stated"
        )
    rope = hf["rope_parameters"]
    full, sliding = rope["full_attention"], rope.get("sliding_attention", {})
    if sliding.get("rope_type", "default") != "default":
        raise NotImplementedError(
            f"mellum sliding_attention rope_type {sliding['rope_type']!r}: "
            "the window layers take plain rope"
        )
    yarn = {}
    if full.get("rope_type", "default") == "yarn":
        yarn = dict(
            rope_yarn_factor=float(full["factor"]),
            rope_yarn_original=int(full["original_max_position_embeddings"]),
            rope_yarn_beta_fast=float(full.get("beta_fast", 32)),
            rope_yarn_beta_slow=float(full.get("beta_slow", 1)),
            rope_yarn_attention_factor=float(full.get("attention_factor") or 0),
        )
    elif full.get("rope_type", "default") != "default":
        raise NotImplementedError(
            f"mellum full_attention rope_type {full['rope_type']!r}")
    share = hf.get("share") or {}
    n_experts = hf["num_experts"]
    width = share.get("router_num_experts", n_experts)
    theta = float(full["rope_theta"])
    window_theta = float(sliding.get("rope_theta", theta))
    return ModelConfig(
        n_layers=n_layers,
        hidden_dim=hf["hidden_size"],
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["head_dim"],
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 131072),
        rope_theta=theta,
        window_rope_theta=0.0 if window_theta == theta else window_theta,
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        qk_norm=True,
        qk_norm_per_head=True,
        tied_embeddings=hf.get("tie_word_embeddings", False),
        n_experts=n_experts,
        n_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_dim=hf["moe_intermediate_size"],
        moe_norm_topk=bool(hf.get("norm_topk_prob", True)),
        moe_aux_loss_coef=hf.get("router_aux_loss_coef", 0.001),
        n_router_experts=0 if width == n_experts else width,
        expert_offset=share.get("rank", 0) * n_experts,
        window_pattern=pattern if "S" in pattern else "",
        attn_window=int(hf.get("sliding_window") or 0) if "S" in pattern else 0,
        **yarn,
    )


def _mellum_config_to_hf(cfg: ModelConfig) -> dict:
    out = _llama_like_config_to_hf(cfg, "mellum")
    out.pop("rope_theta")  # under rope_parameters, a kind of layer each
    pattern = cfg.window_pattern or "F" * cfg.n_layers
    names = {c: t for t, c in _MELLUM_LAYER_TYPES.items()}
    full = {"rope_type": "default", "rope_theta": cfg.rope_theta}
    if cfg.rope_yarn_factor:
        full = {
            "rope_type": "yarn",
            "rope_theta": cfg.rope_theta,
            "factor": cfg.rope_yarn_factor,
            "original_max_position_embeddings": cfg.rope_yarn_original,
            "beta_fast": cfg.rope_yarn_beta_fast,
            "beta_slow": cfg.rope_yarn_beta_slow,
            "attention_factor": cfg.rope_yarn_attention_factor or None,
        }
    out.update(
        architectures=["MellumForCausalLM"],
        hidden_act=cfg.hidden_act,
        attention_bias=False,
        layer_types=[names[c] for c in pattern],
        mlp_layer_types=["sparse"] * cfg.n_layers,
        sliding_window=cfg.attn_window or None,
        use_sliding_window="S" in pattern,
        max_window_layers=0,
        rope_parameters={
            "full_attention": full,
            "sliding_attention": {
                "rope_type": "default",
                "rope_theta": cfg.window_rope_theta or cfg.rope_theta,
            },
        },
        num_experts=cfg.n_experts,
        num_experts_per_tok=cfg.n_experts_per_tok,
        moe_intermediate_size=cfg.moe_intermediate_dim,
        norm_topk_prob=cfg.moe_norm_topk,
        router_aux_loss_coef=cfg.moe_aux_loss_coef,
    )
    if cfg.expert_share:
        out["share"] = {
            "router_num_experts": cfg.router_width,
            "rank": cfg.expert_offset // cfg.n_experts,
        }
    return out


register_hf_family(
    HFFamily(
        "mellum",
        _mellum_config_from_hf,
        _mellum_config_to_hf,
        # olmoe's names: q_norm / k_norm under self_attn (here [head_dim]),
        # mlp.gate, mlp.experts.{e}.{gate,up,down}_proj.
        params_from_sd=_olmoe_params_from_sd,
        params_to_sd=_olmoe_params_to_sd,
    )
)


# ---------------- sdar_moe ----------------
# JetLM/SDAR-*-A3B: the Qwen3-MoE block to the letter (per-head q/k
# RMSNorm, a softmax router over `num_experts`, top-k renormalised, SwiGLU
# experts of `moe_intermediate_size`, no shared expert, no biases; olmoe's
# tensor names) under generation by DIFFUSION OVER BLOCKS (arXiv:2510.06303):
# block-causal attention, in-place prediction, a block of `block_length`
# tokens a decode step.  The published config.json states the layer alone;
# the block's length, the mask token and the sampler's steps are arguments
# of the family's own generate call, and a configuration file carries them
# as keys of their own (the benchmark's lists them under `assumed`).  Of
# the family's two unmasking rules the static one is built
# (`_SDAR_REMASKING`; a configuration that names another is refused).  A
# `share` group cuts the model to one expert-parallel rank, as mellum's
# does.

_SDAR_DEFAULTS = dict(block_length=4, mask_token_id=151669, denoising_steps=4)
_SDAR_REMASKING = "low_confidence_static"


def _sdar_moe_config_from_hf(hf: dict) -> ModelConfig:
    for key, fine in (
        ("attention_bias", False), ("hidden_act", "silu"),
        ("decoder_sparse_step", 1), ("mlp_only_layers", []),
        ("use_sliding_window", False), ("rope_scaling", None),
    ):
        if hf.get(key, fine) != fine:
            raise NotImplementedError(
                f"sdar_moe {key}={hf[key]!r} is not modelled")
    rule = hf.get("remasking_strategy", _SDAR_REMASKING)
    if rule != _SDAR_REMASKING:
        raise NotImplementedError(
            f"sdar_moe remasking_strategy={rule!r}: the block loop reveals "
            f"a fixed count of places a step ({_SDAR_REMASKING!r}); a rule "
            "whose steps follow the draws' confidence is not built")
    share = hf.get("share") or {}
    n_experts = hf["num_experts"]
    width = share.get("router_num_experts", n_experts)
    return ModelConfig(
        n_layers=hf["num_hidden_layers"],
        hidden_dim=hf["hidden_size"],
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim")
        or hf["hidden_size"] // hf["num_attention_heads"],
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 32768),
        rope_theta=float(hf.get("rope_theta", 1000000.0)),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        qk_norm=True,
        qk_norm_per_head=True,
        tied_embeddings=hf.get("tie_word_embeddings", False),
        n_experts=n_experts,
        n_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_dim=hf["moe_intermediate_size"],
        moe_norm_topk=bool(hf.get("norm_topk_prob", True)),
        moe_aux_loss_coef=hf.get("router_aux_loss_coef", 0.001),
        n_router_experts=0 if width == n_experts else width,
        expert_offset=share.get("rank", 0) * n_experts,
        **{k: type(v)(hf.get(k, v)) for k, v in _SDAR_DEFAULTS.items()},
    )


def _sdar_moe_config_to_hf(cfg: ModelConfig) -> dict:
    out = _llama_like_config_to_hf(cfg, "sdar_moe")
    out.update(
        architectures=["SDARMoeForCausalLM"],
        hidden_act=cfg.hidden_act,
        attention_bias=False,
        decoder_sparse_step=1,
        mlp_only_layers=[],
        use_sliding_window=False,
        num_experts=cfg.n_experts,
        num_experts_per_tok=cfg.n_experts_per_tok,
        moe_intermediate_size=cfg.moe_intermediate_dim,
        norm_topk_prob=cfg.moe_norm_topk,
        router_aux_loss_coef=cfg.moe_aux_loss_coef,
        remasking_strategy=_SDAR_REMASKING,
        **{k: getattr(cfg, k) for k in _SDAR_DEFAULTS},
    )
    if cfg.expert_share:
        out["share"] = {
            "router_num_experts": cfg.router_width,
            "rank": cfg.expert_offset // cfg.n_experts,
        }
    return out


register_hf_family(
    HFFamily(
        "sdar_moe",
        _sdar_moe_config_from_hf,
        _sdar_moe_config_to_hf,
        # Qwen3-MoE's names, which are olmoe's: self_attn.{q,k}_norm
        # ([head_dim]), mlp.gate, mlp.experts.{e}.{gate,up,down}_proj.
        params_from_sd=_olmoe_params_from_sd,
        params_to_sd=_olmoe_params_to_sd,
    )
)


# ---------------- qwen3_next ----------------
# A hybrid layer pattern: layer i is gated softmax attention when (i + 1) %
# full_attention_interval == 0, else Gated DeltaNet (linear attention);
# every layer's MLP is a mixture of experts plus one gated shared expert.
# HF fuses the DeltaNet projections per KEY head (`in_proj_qkvz`: [q, k,
# v x r, z x r], `in_proj_ba`: [b x r, a x r], r = value heads per key
# head) and the attention query with its gate per head (`q_proj`: [q,
# gate]); ours keep q | k | v, z, b | a and wq, wqg apart, heads contiguous.
#
# A `share` group in the config (not an HF key; benchmark configurations
# carry it) cuts the model to one expert-parallel rank: `num_experts` is
# then the number HELD here, of `share.router_num_experts` the router
# scores, starting at expert `share.rank * num_experts`.  A published
# config.json has no such group: share 1 of 1, the whole model.


def _qwen3_next_config_from_hf(hf: dict) -> ModelConfig:
    for key, fine in (
        ("mlp_only_layers", []), ("decoder_sparse_step", 1),
        ("rope_scaling", None), ("attention_bias", False),
        ("use_sliding_window", False), ("hidden_act", "silu"),
    ):
        if hf.get(key, fine) != fine:
            raise NotImplementedError(
                f"qwen3_next {key}={hf[key]!r} is not modelled"
            )
    share = hf.get("share") or {}
    n_experts = hf["num_experts"]
    width = share.get("router_num_experts", n_experts)
    return ModelConfig(
        n_layers=hf["num_hidden_layers"],
        hidden_dim=hf["hidden_size"],
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["head_dim"],
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 262144),
        rope_theta=hf.get("rope_theta", 10000000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        rms_norm_offset=True,
        qk_norm=True,
        qk_norm_per_head=True,
        attn_gate=True,
        rotary_dim=int(hf["head_dim"] * hf.get("partial_rotary_factor", 1.0)),
        tied_embeddings=hf.get("tie_word_embeddings", False),
        n_experts=n_experts,
        n_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_dim=hf["moe_intermediate_size"],
        moe_norm_topk=bool(hf.get("norm_topk_prob", True)),
        moe_aux_loss_coef=hf.get("router_aux_loss_coef", 0.001),
        shared_expert_dim=hf.get("shared_expert_intermediate_size", 0),
        n_router_experts=0 if width == n_experts else width,
        expert_offset=share.get("rank", 0) * n_experts,
        full_attn_interval=hf["full_attention_interval"],
        linear_n_k_heads=hf["linear_num_key_heads"],
        linear_n_v_heads=hf["linear_num_value_heads"],
        linear_k_head_dim=hf["linear_key_head_dim"],
        linear_v_head_dim=hf["linear_value_head_dim"],
        linear_conv_kernel=hf["linear_conv_kernel_dim"],
    )


def _qwen3_next_config_to_hf(cfg: ModelConfig) -> dict:
    out = _llama_like_config_to_hf(cfg, "qwen3_next")
    out.update(
        architectures=["Qwen3NextForCausalLM"],
        hidden_act=cfg.hidden_act,
        attention_bias=False,
        partial_rotary_factor=(cfg.rotary_dim or cfg.head_dim) / cfg.head_dim,
        full_attention_interval=cfg.full_attn_interval,
        linear_num_key_heads=cfg.linear_n_k_heads,
        linear_num_value_heads=cfg.linear_n_v_heads,
        linear_key_head_dim=cfg.linear_k_head_dim,
        linear_value_head_dim=cfg.linear_v_head_dim,
        linear_conv_kernel_dim=cfg.linear_conv_kernel,
        decoder_sparse_step=1,
        mlp_only_layers=[],
        num_experts=cfg.n_experts,
        num_experts_per_tok=cfg.n_experts_per_tok,
        moe_intermediate_size=cfg.moe_intermediate_dim,
        shared_expert_intermediate_size=cfg.shared_expert_dim,
        norm_topk_prob=cfg.moe_norm_topk,
        router_aux_loss_coef=cfg.moe_aux_loss_coef,
    )
    if cfg.expert_share:
        out["share"] = {
            "router_num_experts": cfg.router_width,
            "rank": cfg.expert_offset // cfg.n_experts,
        }
    return out


_Q3N = "model.layers.{}."
# ours <- HF name under the layer, transposed ([out, in] -> [in, out]).
_Q3N_LAYER = (  # every layer
    ("ln1", "input_layernorm.weight", False),
    ("ln2", "post_attention_layernorm.weight", False),
    ("router", "mlp.gate.weight", True),
    ("ws_g", "mlp.shared_expert.gate_proj.weight", True),
    ("ws_u", "mlp.shared_expert.up_proj.weight", True),
    ("ws_d", "mlp.shared_expert.down_proj.weight", True),
    ("ws_gate", "mlp.shared_expert_gate.weight", True),
)
_Q3N_FULL = (  # full-attention layers (wq / wqg: the fused q_proj, below)
    ("wk", "self_attn.k_proj.weight", True),
    ("wv", "self_attn.v_proj.weight", True),
    ("wo", "self_attn.o_proj.weight", True),
    ("q_norm", "self_attn.q_norm.weight", False),
    ("k_norm", "self_attn.k_norm.weight", False),
)
_Q3N_LINEAR = (  # linear layers (the fused in_proj_*: below)
    ("la_A_log", "linear_attn.A_log", False),
    ("la_dt_bias", "linear_attn.dt_bias", False),
    ("la_norm", "linear_attn.norm.weight", False),
    ("la_wo", "linear_attn.out_proj.weight", True),
)


def _q3n_layers(cfg):
    """(all, full-attention, linear) HF layer numbers."""
    n = cfg.full_attn_interval
    every = list(range(cfg.n_layers))
    full = [i for i in every if (i + 1) % n == 0]
    return every, full, [i for i in every if (i + 1) % n]


def _q3n_fused_sizes(cfg):
    """Per key head, the rows of in_proj_qkvz ([q, k, v, z]) and of
    in_proj_ba ([b, a])."""
    r = cfg.linear_n_v_heads // cfg.linear_n_k_heads
    dk, dv = cfg.linear_k_head_dim, cfg.linear_v_head_dim
    return (dk, dk, r * dv, r * dv), (r, r)


def _qwen3_next_params_from_sd(cfg, sd, dtype=None):
    import jax.numpy as jnp

    dtype = dtype or cfg.dtype

    def get(name):
        if name not in sd:
            raise KeyError(f"missing tensor {name!r} in checkpoint")
        return np.asarray(sd[name], np.float32)

    def stack(layers, fn):
        return jnp.asarray(np.stack([fn(_Q3N.format(i)) for i in layers]), dtype)

    every, full, linear = _q3n_layers(cfg)
    blocks = {}
    for group, layers in (
        (_Q3N_LAYER, every), (_Q3N_FULL, full), (_Q3N_LINEAR, linear)
    ):
        for ours, theirs, transpose in group:
            blocks[ours] = stack(
                layers,
                lambda pre: get(pre + theirs).T if transpose else get(pre + theirs),
            )
    hq, hd, d = cfg.n_q_heads, cfg.head_dim, cfg.hidden_dim

    def q_proj(pre, part):  # [hq, (q | gate), hd, D] -> [D, hq * hd]
        w = get(pre + "self_attn.q_proj.weight").reshape(hq, 2, hd, d)
        return w[:, part].reshape(hq * hd, d).T

    blocks["wq"] = stack(full, lambda pre: q_proj(pre, 0))
    blocks["wqg"] = stack(full, lambda pre: q_proj(pre, 1))
    hk = cfg.linear_n_k_heads
    qkvz_sizes, ba_sizes = _q3n_fused_sizes(cfg)

    def unfuse(pre, name, sizes, parts):
        """Rows [hk, sum(sizes), D] of a per-key-head fused projection ->
        the chosen parts, each with its heads contiguous: [D, sum]."""
        w = get(pre + name).reshape(hk, sum(sizes), d)
        cuts = np.cumsum((0,) + sizes)
        return np.concatenate(
            [w[:, cuts[j]: cuts[j + 1]].reshape(-1, d) for j in parts]
        ).T

    qkvz, ba = "linear_attn.in_proj_qkvz.weight", "linear_attn.in_proj_ba.weight"
    blocks["la_wqkv"] = stack(
        linear, lambda pre: unfuse(pre, qkvz, qkvz_sizes, (0, 1, 2)))
    blocks["la_wz"] = stack(
        linear, lambda pre: unfuse(pre, qkvz, qkvz_sizes, (3,)))
    blocks["la_wba"] = stack(
        linear, lambda pre: unfuse(pre, ba, ba_sizes, (0, 1)))
    blocks["la_conv"] = stack(  # [C, 1, K] -> [K, C]
        linear, lambda pre: get(pre + "linear_attn.conv1d.weight")[:, 0].T)
    for ours, theirs in _OLMOE_EXPERT_LEAVES:
        blocks[ours] = stack(
            every,
            lambda pre: np.stack([
                get(f"{pre}mlp.experts.{cfg.expert_offset + e}.{theirs}.weight").T
                for e in range(cfg.n_experts)
            ]),
        )
    return {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), dtype),
        "blocks": blocks,
        "final_ln": jnp.asarray(get("model.norm.weight"), dtype),
        "lm_head": jnp.asarray(get("lm_head.weight").T, dtype),
    }


def _qwen3_next_params_to_sd(cfg, params):
    from areal_tpu.base.distributed import to_host

    def host(x):
        return to_host(x).astype(np.float32, copy=False)

    blocks = {k: host(v) for k, v in params["blocks"].items()}
    out = {
        "model.embed_tokens.weight": host(params["embed"]),
        "model.norm.weight": host(params["final_ln"]),
        "lm_head.weight": np.ascontiguousarray(host(params["lm_head"]).T),
    }
    every, full, linear = _q3n_layers(cfg)
    for group, layers in (
        (_Q3N_LAYER, every), (_Q3N_FULL, full), (_Q3N_LINEAR, linear)
    ):
        for ours, theirs, transpose in group:
            for j, i in enumerate(layers):
                w = blocks[ours][j]
                out[_Q3N.format(i) + theirs] = (
                    np.ascontiguousarray(w.T) if transpose else w
                )
    hq, hd, d = cfg.n_q_heads, cfg.head_dim, cfg.hidden_dim
    for j, i in enumerate(full):
        parts = [blocks[n][j].T.reshape(hq, 1, hd, d) for n in ("wq", "wqg")]
        out[_Q3N.format(i) + "self_attn.q_proj.weight"] = np.concatenate(
            parts, axis=1).reshape(2 * hq * hd, d)
    hk = cfg.linear_n_k_heads
    kd, vd, hv = cfg.linear_key_dim, cfg.linear_value_dim, cfg.linear_n_v_heads

    def fuse(parts):  # each [sum over heads, D] -> per key head, side by side
        return np.concatenate(
            [p.reshape(hk, -1, d) for p in parts], axis=1).reshape(-1, d)

    for j, i in enumerate(linear):
        pre = _Q3N.format(i) + "linear_attn."
        qkv, ba = blocks["la_wqkv"][j].T, blocks["la_wba"][j].T
        out[pre + "in_proj_qkvz.weight"] = fuse(
            [qkv[:kd], qkv[kd: 2 * kd], qkv[2 * kd:], blocks["la_wz"][j].T])
        out[pre + "in_proj_ba.weight"] = fuse([ba[:hv], ba[hv:]])
        out[pre + "conv1d.weight"] = np.ascontiguousarray(
            blocks["la_conv"][j].T)[:, None]
    for ours, theirs in _OLMOE_EXPERT_LEAVES:
        for i in every:
            for e in range(cfg.n_experts):
                out[
                    f"{_Q3N.format(i)}mlp.experts.{cfg.expert_offset + e}."
                    f"{theirs}.weight"
                ] = np.ascontiguousarray(blocks[ours][i, e].T)
    return out


register_hf_family(
    HFFamily(
        "qwen3_next",
        _qwen3_next_config_from_hf,
        _qwen3_next_config_to_hf,
        params_from_sd=_qwen3_next_params_from_sd,
        params_to_sd=_qwen3_next_params_to_sd,
    )
)


# ---------------- olmo_hybrid ----------------
# allenai/Olmo-Hybrid-7B: `layer_types` gives every layer its mixer —
# "linear_attention", Flash Linear Attention's `GatedDeltaNet` (the rule of
# `models/linear_attention.py`, as many key as value heads, d_v = 2 d_k, and
# with `linear_allow_neg_eigval` beta = 2 sigmoid(b)), or "full_attention",
# softmax attention with as many key as query heads — a dense SwiGLU MLP
# behind each, the head untied.  Three conventions the config has NO key
# for are the family's (OLMo 2, Olmo 3) and stated as assumptions in
# `benchmark/configs/olmo-hybrid-7b-l4-v8.json`, one field each so that a
# correction from the published module is a change of data: the norms sit
# on a branch's OUTPUT (`branch_norm`: h = x + norm(mixer(x)), y = h +
# norm(mlp(h)); `ln1` / `ln2` are `post_attention_layernorm` /
# `post_feedforward_layernorm`), the full layers norm q and k over the
# WHOLE projection (`qk_norm`, olmoe's), and with `rope_parameters.
# rope_theta` null they take no positions (`pos_emb` "none").  The tensor
# names are assumed too (OLMo 2's for the block, FLA's for the mixer under
# `linear_attn.`; no network to re-read the module).

_OLMOH_LAYER_TYPES = ("linear_attention", "full_attention")


def _olmo_hybrid_interval(types, n_layers: int) -> int:
    """`layer_types` as `full_attn_interval`: periods of n - 1 linear
    layers and one full layer."""
    if len(types) != n_layers or set(types) - set(_OLMOH_LAYER_TYPES):
        raise ValueError(
            f"layer_types {types!r} is not {n_layers} of "
            f"{list(_OLMOH_LAYER_TYPES)}"
        )
    n = types.index("full_attention") + 1 if "full_attention" in types else 0
    period = ["linear_attention"] * (n - 1) + ["full_attention"]
    if n < 2 or n_layers % n or list(types) != period * (n_layers // n):
        raise NotImplementedError(
            f"olmo_hybrid layer_types {types!r}: the layers are whole "
            "periods of linear_attention layers closed by one full_attention "
            "layer"
        )
    return n


def _olmo_hybrid_config_from_hf(hf: dict) -> ModelConfig:
    for key, fine in (
        ("attention_bias", False), ("hidden_act", "silu"),
        ("rope_scaling", None), ("clip_qkv", None),
    ):
        if hf.get(key, fine) != fine:
            raise NotImplementedError(
                f"olmo_hybrid {key}={hf[key]!r} is not modelled"
            )
    theta = (hf.get("rope_parameters") or {}).get(
        "rope_theta", hf.get("rope_theta"))
    return ModelConfig(
        n_layers=hf["num_hidden_layers"],
        hidden_dim=hf["hidden_size"],
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=hf.get("head_dim")
        or hf["hidden_size"] // hf["num_attention_heads"],
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 65536),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        tied_embeddings=bool(hf.get("tie_word_embeddings", False)),
        # A null theta parameterises no rotary table: no positions in the
        # full layers; order reaches the model through the linear layers.
        pos_emb="none" if theta is None else "rope",
        **({} if theta is None else {"rope_theta": float(theta)}),
        qk_norm=True,
        branch_norm="output",
        full_attn_interval=_olmo_hybrid_interval(
            hf["layer_types"], hf["num_hidden_layers"]),
        linear_n_k_heads=hf["linear_num_key_heads"],
        linear_n_v_heads=hf["linear_num_value_heads"],
        linear_k_head_dim=hf["linear_key_head_dim"],
        linear_v_head_dim=hf["linear_value_head_dim"],
        linear_conv_kernel=hf["linear_conv_kernel_dim"],
        linear_neg_eigval=bool(hf.get("linear_allow_neg_eigval", False)),
    )


def _olmo_hybrid_config_to_hf(cfg: ModelConfig) -> dict:
    n = cfg.full_attn_interval
    return {
        "model_type": "olmo_hybrid",
        "architectures": ["OlmoHybridForCausalLM"],
        "torch_dtype": "bfloat16",
        "num_hidden_layers": cfg.n_layers,
        "layer_types": (
            ["linear_attention"] * (n - 1) + ["full_attention"]
        ) * cfg.n_periods,
        "hidden_size": cfg.hidden_dim,
        "intermediate_size": cfg.intermediate_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_position_embeddings,
        "hidden_act": "silu",
        "attention_bias": False,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tied_embeddings,
        "rope_parameters": {
            "rope_theta": None if cfg.pos_emb == "none" else cfg.rope_theta},
        "linear_num_key_heads": cfg.linear_n_k_heads,
        "linear_num_value_heads": cfg.linear_n_v_heads,
        "linear_key_head_dim": cfg.linear_k_head_dim,
        "linear_value_head_dim": cfg.linear_v_head_dim,
        "linear_conv_kernel_dim": cfg.linear_conv_kernel,
        "linear_allow_neg_eigval": cfg.linear_neg_eigval,
    }


_OLMOH = "model.layers.{}."
# ours <- the name behind the layer's prefix, transposed ([out, in] -> [in,
# out]); the mixer's fused leaves (`la_wqkv`, `la_wba`, `la_conv`) below.
_OLMOH_LAYER = (  # every layer
    ("ln1", "post_attention_layernorm.weight", False),
    ("ln2", "post_feedforward_layernorm.weight", False),
    ("wg", "mlp.gate_proj.weight", True),
    ("wu", "mlp.up_proj.weight", True),
    ("wd", "mlp.down_proj.weight", True),
)
_OLMOH_FULL = (
    ("wq", "self_attn.q_proj.weight", True),
    ("wk", "self_attn.k_proj.weight", True),
    ("wv", "self_attn.v_proj.weight", True),
    ("wo", "self_attn.o_proj.weight", True),
    ("q_norm", "self_attn.q_norm.weight", False),
    ("k_norm", "self_attn.k_norm.weight", False),
)
_OLMOH_LINEAR = (
    ("la_wz", "linear_attn.g_proj.weight", True),
    ("la_A_log", "linear_attn.A_log", False),
    ("la_dt_bias", "linear_attn.dt_bias", False),
    ("la_norm", "linear_attn.o_norm.weight", False),
    ("la_wo", "linear_attn.o_proj.weight", True),
)
# A fused leaf <- its parts, side by side: la_wqkv = q | k | v, la_wba = b |
# a (projections, transposed), la_conv = the three depthwise convs' taps.
_OLMOH_FUSED = {
    "la_wqkv": ("q_proj", "k_proj", "v_proj"),
    "la_wba": ("b_proj", "a_proj"),
    "la_conv": ("q_conv1d", "k_conv1d", "v_conv1d"),
}


def _olmo_hybrid_groups(cfg):
    every, full, linear = _q3n_layers(cfg)
    return (
        (_OLMOH_LAYER, every), (_OLMOH_FULL, full), (_OLMOH_LINEAR, linear),
    ), linear


def _olmo_hybrid_params_from_sd(cfg, sd, dtype=None):
    import jax.numpy as jnp

    dtype = dtype or cfg.dtype

    def get(name):
        if name not in sd:
            raise KeyError(f"missing tensor {name!r} in checkpoint")
        return np.asarray(sd[name], np.float32)

    def stack(layers, fn):
        return jnp.asarray(
            np.stack([fn(_OLMOH.format(i)) for i in layers]), dtype)

    groups, linear = _olmo_hybrid_groups(cfg)
    blocks = {}
    for group, layers in groups:
        for ours, theirs, t in group:
            blocks[ours] = stack(
                layers,
                lambda pre: get(pre + theirs).T if t else get(pre + theirs))

    def part(pre, name):  # -> [in or K, out or C]
        w = get(f"{pre}linear_attn.{name}.weight")
        return w[:, 0].T if w.ndim == 3 else w.T  # a conv's [C, 1, K]

    for ours, parts in _OLMOH_FUSED.items():
        blocks[ours] = stack(linear, lambda pre: np.concatenate(
            [part(pre, name) for name in parts], axis=1))
    params = {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), dtype),
        "blocks": blocks,
        "final_ln": jnp.asarray(get("model.norm.weight"), dtype),
    }
    if not cfg.tied_embeddings:
        params["lm_head"] = jnp.asarray(get("lm_head.weight").T, dtype)
    return params


def _olmo_hybrid_params_to_sd(cfg, params):
    from areal_tpu.base.distributed import to_host

    def host(x):
        return to_host(x).astype(np.float32, copy=False)

    blocks = {n: host(w) for n, w in params["blocks"].items()}
    out = {
        "model.embed_tokens.weight": host(params["embed"]),
        "model.norm.weight": host(params["final_ln"]),
    }
    if not cfg.tied_embeddings:
        out["lm_head.weight"] = np.ascontiguousarray(
            host(params["lm_head"]).T)
    groups, linear = _olmo_hybrid_groups(cfg)
    for group, layers in groups:
        for ours, theirs, t in group:
            for j, i in enumerate(layers):
                w = blocks[ours][j]
                out[_OLMOH.format(i) + theirs] = (
                    np.ascontiguousarray(w.T) if t else w)
    kd, vd = cfg.linear_key_dim, cfg.linear_value_dim
    hv = cfg.linear_n_v_heads
    widths = {
        "la_wqkv": (kd, kd, vd), "la_wba": (hv, hv), "la_conv": (kd, kd, vd)}
    for ours, parts in _OLMOH_FUSED.items():
        cuts = np.cumsum((0,) + widths[ours])
        for j, i in enumerate(linear):
            for n, name in enumerate(parts):
                w = np.ascontiguousarray(
                    blocks[ours][j][:, cuts[n]: cuts[n + 1]].T)
                out[f"{_OLMOH.format(i)}linear_attn.{name}.weight"] = (
                    w[:, None] if ours == "la_conv" else w)
    return out


register_hf_family(
    HFFamily(
        "olmo_hybrid",
        _olmo_hybrid_config_from_hf,
        _olmo_hybrid_config_to_hf,
        params_from_sd=_olmo_hybrid_params_from_sd,
        params_to_sd=_olmo_hybrid_params_to_sd,
    )
)


# ---------------- glm4_moe_lite ----------------
# zai-org/GLM-4.7-Flash: the deepseek_v3 block.  Latent attention (MLA):
# `q_a_proj` -> `q_a_layernorm` -> `q_b_proj` gives each head a query of
# [nope | rope] columns; `kv_a_proj_with_mqa` gives [latent | one rope key
# all heads share], the latent goes through `kv_a_layernorm` and `kv_b_proj`
# gives each head [k_nope | v].  The first `first_k_dense_replace` layers
# have a dense MLP; the others a sigmoid router (`mlp.gate.weight`, with the
# untrained choice bias `mlp.gate.e_score_correction_bias`), routed experts
# and `n_shared_experts` ungated shared experts fused into one MLP.
#
# HF applies the rotary embedding to INTERLEAVED pairs (`rope_interleave`:
# columns (0, 1), (2, 3), ...); ours rotates halves (columns j and j + r/2).
# The converter permutes the rope columns of `wq_b` and `wkv_a` on the way
# in ([0, 2, 4, ..., 1, 3, 5, ...]) and back on the way out: q_pe . k_pe is
# the same sum either way, and the cache's rope columns are in OUR order.
# Ours keep `kv_b_proj` as two leaves, `wk_b` and `wv_b`, heads contiguous:
# the absorbed decode step multiplies by each alone.
#
# The multi-token-prediction layer (`num_nextn_predict_layers`, HF layer
# `num_hidden_layers`) is not modelled: it enters no next-token logit, and
# its tensors are neither read nor written.  A `share` group cuts the model
# to one expert-parallel rank as for qwen3_next: `n_routed_experts` is then
# the number HELD here of `share.router_num_experts`.


def _glm4_moe_lite_config_from_hf(hf: dict) -> ModelConfig:
    for key, fine in (
        ("rope_scaling", None), ("attention_bias", False),
        ("hidden_act", "silu"), ("n_group", 1), ("topk_group", 1),
        ("topk_method", "noaux_tc"), ("partial_rotary_factor", 1),
        ("scoring_func", "sigmoid"), ("moe_layer_freq", 1),
    ):
        if hf.get(key, fine) != fine:
            raise NotImplementedError(
                f"glm4_moe_lite {key}={hf[key]!r} is not modelled"
            )
    share = hf.get("share") or {}
    n_experts = hf["n_routed_experts"]
    width = share.get("router_num_experts", n_experts)
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    # What a benchmark configuration assumes where the published file is
    # silent (`benchmark.assumed`); a checkpoint's config.json has none.
    assumed = (hf.get("benchmark") or {}).get("assumed") or {}
    return ModelConfig(
        n_layers=hf["num_hidden_layers"],
        hidden_dim=hf["hidden_size"],
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        head_dim=nope + rope,
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 202752),
        rope_theta=hf.get("rope_theta", 1000000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        tied_embeddings=hf.get("tie_word_embeddings", False),
        q_lora_rank=hf["q_lora_rank"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=nope,
        qk_rope_head_dim=rope,
        v_head_dim=hf["v_head_dim"],
        first_k_dense=hf.get("first_k_dense_replace", 0),
        n_experts=n_experts,
        n_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_dim=hf["moe_intermediate_size"],
        moe_norm_topk=bool(hf.get("norm_topk_prob", True)),
        moe_aux_loss_coef=0.0,
        moe_score_func="sigmoid",
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
        router_bias_init_std=float(assumed.get("router_bias_init_std", 0.0)),
        shared_expert_dim=(
            hf.get("n_shared_experts", 0) * hf["moe_intermediate_size"]
        ),
        shared_expert_gated=False,
        n_router_experts=0 if width == n_experts else width,
        expert_offset=share.get("rank", 0) * n_experts,
    )


def _glm4_moe_lite_config_to_hf(cfg: ModelConfig) -> dict:
    out = _llama_like_config_to_hf(cfg, "glm4_moe_lite")
    out.pop("head_dim")  # not a key of this family: nope + rope
    out.update(
        architectures=["Glm4MoeLiteForCausalLM"],
        hidden_act=cfg.hidden_act,
        attention_bias=False,
        rope_scaling=None,
        partial_rotary_factor=1,
        q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim,
        first_k_dense_replace=cfg.first_k_dense,
        n_routed_experts=cfg.n_experts,
        num_experts_per_tok=cfg.n_experts_per_tok,
        moe_intermediate_size=cfg.moe_intermediate_dim,
        n_shared_experts=cfg.shared_expert_dim // cfg.moe_intermediate_dim,
        norm_topk_prob=cfg.moe_norm_topk,
        routed_scaling_factor=cfg.moe_routed_scale,
        topk_method="noaux_tc",
        n_group=1,
        topk_group=1,
    )
    if cfg.expert_share:
        out["share"] = {
            "router_num_experts": cfg.router_width,
            "rank": cfg.expert_offset // cfg.n_experts,
        }
    return out


_GLM = "model.layers.{}."
# ours <- HF name under the layer, transposed ([out, in] -> [in, out]).
_GLM_LAYER = (  # every layer (`wq_b`, `wkv_a`, `wk_b`, `wv_b`: below)
    ("ln1", "input_layernorm.weight", False),
    ("ln2", "post_attention_layernorm.weight", False),
    ("wq_a", "self_attn.q_a_proj.weight", True),
    ("q_a_norm", "self_attn.q_a_layernorm.weight", False),
    ("kv_a_norm", "self_attn.kv_a_layernorm.weight", False),
    ("wo", "self_attn.o_proj.weight", True),
)
_GLM_DENSE = (
    ("wg", "mlp.gate_proj.weight", True),
    ("wu", "mlp.up_proj.weight", True),
    ("wd", "mlp.down_proj.weight", True),
)
_GLM_SPARSE = (
    ("router", "mlp.gate.weight", True),
    ("router_bias", "mlp.gate.e_score_correction_bias", False),
    ("ws_g", "mlp.shared_experts.gate_proj.weight", True),
    ("ws_u", "mlp.shared_experts.up_proj.weight", True),
    ("ws_d", "mlp.shared_experts.down_proj.weight", True),
)


def _glm_rope_order(cfg, inverse=False):
    """Columns of a rope part: HF's interleaved pairs -> our halves."""
    r = cfg.qk_rope_head_dim
    order = np.concatenate([np.arange(0, r, 2), np.arange(1, r, 2)])
    return np.argsort(order) if inverse else order


def _glm4_moe_lite_params_from_sd(cfg, sd, dtype=None):
    import jax.numpy as jnp

    dtype = dtype or cfg.dtype
    h = cfg.n_q_heads
    nope, c, vd = cfg.qk_nope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
    order = _glm_rope_order(cfg)

    def get(name):
        if name not in sd:
            raise KeyError(f"missing tensor {name!r} in checkpoint")
        return np.asarray(sd[name], np.float32)

    def wq_b(pre):  # [h, (nope | rope), rq] -> [rq, h * (nope | rope')]
        w = get(pre + "self_attn.q_b_proj.weight").reshape(h, cfg.head_dim, -1)
        w = np.concatenate([w[:, :nope], w[:, nope:][:, order]], axis=1)
        return w.reshape(h * cfg.head_dim, -1).T

    def wkv_a(pre):  # [(latent | rope), D] -> [D, (latent | rope')]
        w = get(pre + "self_attn.kv_a_proj_with_mqa.weight")
        return np.concatenate([w[:c], w[c:][order]]).T

    def kv_b(pre, part):  # [h, (nope | v), c] -> [c, h * nope] or [c, h * v]
        w = get(pre + "self_attn.kv_b_proj.weight").reshape(h, nope + vd, c)
        w = w[:, :nope] if part == 0 else w[:, nope:]
        return w.reshape(-1, c).T

    def layer_leaves(layers, extra):
        def stack(fn):
            return jnp.asarray(
                np.stack([fn(_GLM.format(i)) for i in layers]), dtype)

        out = {
            ours: stack(
                lambda pre: get(pre + theirs).T if t else get(pre + theirs))
            for ours, theirs, t in _GLM_LAYER + extra
        }
        out.update(
            wq_b=stack(wq_b), wkv_a=stack(wkv_a),
            wk_b=stack(lambda pre: kv_b(pre, 0)),
            wv_b=stack(lambda pre: kv_b(pre, 1)),
        )
        return out, stack

    k = cfg.first_k_dense
    blocks, stack = layer_leaves(range(k, cfg.n_layers), _GLM_SPARSE)
    for ours, theirs in _OLMOE_EXPERT_LEAVES:
        blocks[ours] = stack(
            lambda pre: np.stack([
                get(f"{pre}mlp.experts.{cfg.expert_offset + e}.{theirs}.weight").T
                for e in range(cfg.n_experts)
            ]))
    if k:
        lead, _ = layer_leaves(range(k), _GLM_DENSE)
        blocks.update({DENSE_PREFIX + n: w for n, w in lead.items()})
    return {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), dtype),
        "blocks": blocks,
        "final_ln": jnp.asarray(get("model.norm.weight"), dtype),
        "lm_head": jnp.asarray(get("lm_head.weight").T, dtype),
    }


def _glm4_moe_lite_params_to_sd(cfg, params):
    from areal_tpu.base.distributed import to_host

    def host(x):
        return to_host(x).astype(np.float32, copy=False)

    h, nope, c = cfg.n_q_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    back = _glm_rope_order(cfg, inverse=True)
    every = {n: host(w) for n, w in params["blocks"].items()}
    out = {
        "model.embed_tokens.weight": host(params["embed"]),
        "model.norm.weight": host(params["final_ln"]),
        "lm_head.weight": np.ascontiguousarray(host(params["lm_head"]).T),
    }

    def write(blocks, layers, extra):
        for j, i in enumerate(layers):
            pre = _GLM.format(i)
            for ours, theirs, t in _GLM_LAYER + extra:
                w = blocks[ours][j]
                out[pre + theirs] = np.ascontiguousarray(w.T) if t else w
            w = blocks["wq_b"][j].T.reshape(h, cfg.head_dim, -1)
            out[pre + "self_attn.q_b_proj.weight"] = np.concatenate(
                [w[:, :nope], w[:, nope:][:, back]], axis=1
            ).reshape(h * cfg.head_dim, -1)
            w = blocks["wkv_a"][j].T
            out[pre + "self_attn.kv_a_proj_with_mqa.weight"] = np.concatenate(
                [w[:c], w[c:][back]])
            out[pre + "self_attn.kv_b_proj.weight"] = np.concatenate(
                [blocks["wk_b"][j].T.reshape(h, nope, c),
                 blocks["wv_b"][j].T.reshape(h, cfg.v_head_dim, c)], axis=1
            ).reshape(-1, c)

    k = cfg.first_k_dense
    sparse = range(k, cfg.n_layers)
    write(every, sparse, _GLM_SPARSE)
    for ours, theirs in _OLMOE_EXPERT_LEAVES:
        for j, i in enumerate(sparse):
            for e in range(cfg.n_experts):
                out[
                    f"{_GLM.format(i)}mlp.experts.{cfg.expert_offset + e}."
                    f"{theirs}.weight"
                ] = np.ascontiguousarray(every[ours][j, e].T)
    if k:
        lead = {
            n[len(DENSE_PREFIX):]: w for n, w in every.items()
            if n.startswith(DENSE_PREFIX)
        }
        write(lead, range(k), _GLM_DENSE)
    return out


register_hf_family(
    HFFamily(
        "glm4_moe_lite",
        _glm4_moe_lite_config_from_hf,
        _glm4_moe_lite_config_to_hf,
        params_from_sd=_glm4_moe_lite_params_from_sd,
        params_to_sd=_glm4_moe_lite_params_to_sd,
    )
)


# ---------------- nemotron_h ----------------
# nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B: `hybrid_override_pattern` gives
# every layer ONE kind of `mixer` behind ONE norm (`backbone.layers.N.norm`):
# "M" Mamba-2 (`in_proj` -> [z | x B C | dt], a depthwise `conv1d` WITH
# bias, `A_log` / `D` / `dt_bias` per head, a gated RMSNorm over groups,
# `out_proj`), "E" a mixture of ungated relu² experts (`up_proj`,
# `down_proj`) behind the deepseek_v3 sigmoid router (`gate.weight`,
# `gate.e_score_correction_bias`) plus one ungated shared expert, "*"
# attention WITHOUT a positional embedding (`rope_theta` and
# `partial_rotary_factor` are keys the published module does not read; they
# are kept as published).  d_inner = mamba_num_heads x mamba_head_dim
# (`expand` sizes nothing).  The dense-MLP kind "-" is refused by name.  A
# `share` group cuts the model to one expert-parallel rank as for
# glm4_moe_lite: `n_routed_experts` is then the number HELD here of
# `share.router_num_experts`.


def _nemotron_h_config_from_hf(hf: dict) -> ModelConfig:
    for key, fine in (
        ("attention_bias", False), ("mlp_bias", False), ("use_bias", False),
        ("mamba_proj_bias", False), ("use_conv_bias", True),
        ("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
        ("n_group", 1), ("topk_group", 1), ("sliding_window", None),
        ("residual_in_fp32", False), ("n_shared_experts", 1),
        ("tie_word_embeddings", False),
    ):
        if hf.get(key, fine) != fine:
            raise NotImplementedError(
                f"nemotron_h {key}={hf[key]!r} is not modelled"
            )
    share = hf.get("share") or {}
    n_experts = hf["n_routed_experts"]
    width = share.get("router_num_experts", n_experts)
    assumed = (hf.get("benchmark") or {}).get("assumed") or {}
    return ModelConfig(
        n_layers=hf["num_hidden_layers"],
        hidden_dim=hf["hidden_size"],
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["head_dim"],
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 262144),
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("layer_norm_epsilon", 1e-5),
        hidden_act="relu2",
        mlp_gated=False,
        pos_emb="none",
        layer_pattern=hf["hybrid_override_pattern"],
        ssm_n_heads=hf["mamba_num_heads"],
        ssm_head_dim=hf["mamba_head_dim"],
        ssm_n_groups=hf["n_groups"],
        ssm_state_dim=hf["ssm_state_size"],
        ssm_conv_kernel=hf["conv_kernel"],
        ssm_chunk=hf.get("chunk_size", 128),
        ssm_dt_min=hf.get("time_step_min", 0.001),
        ssm_dt_max=hf.get("time_step_max", 0.1),
        ssm_dt_floor=hf.get("time_step_floor", 1e-4),
        n_experts=n_experts,
        n_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_dim=hf["moe_intermediate_size"],
        moe_norm_topk=bool(hf.get("norm_topk_prob", True)),
        moe_aux_loss_coef=0.0,
        moe_score_func="sigmoid",
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
        router_bias_init_std=float(assumed.get("router_bias_init_std", 0.0)),
        shared_expert_dim=hf["moe_shared_expert_intermediate_size"],
        shared_expert_gated=False,
        n_router_experts=0 if width == n_experts else width,
        expert_offset=share.get("rank", 0) * n_experts,
    )


def _nemotron_h_config_to_hf(cfg: ModelConfig) -> dict:
    out = {
        "model_type": "nemotron_h",
        "architectures": ["NemotronHForCausalLM"],
        "torch_dtype": "bfloat16",
        "num_hidden_layers": cfg.n_layers,
        "hybrid_override_pattern": cfg.layer_pattern,
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "intermediate_size": cfg.intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_position_embeddings,
        "rope_theta": cfg.rope_theta,
        "partial_rotary_factor": 1,
        "layer_norm_epsilon": cfg.rms_norm_eps,
        "norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": False,
        "attention_bias": False,
        "mlp_bias": False,
        "use_bias": False,
        "mamba_proj_bias": False,
        "use_conv_bias": True,
        "mlp_hidden_act": "relu2",
        "mamba_hidden_act": "silu",
        "mamba_num_heads": cfg.ssm_n_heads,
        "mamba_head_dim": cfg.ssm_head_dim,
        "n_groups": cfg.ssm_n_groups,
        "ssm_state_size": cfg.ssm_state_dim,
        "conv_kernel": cfg.ssm_conv_kernel,
        "chunk_size": cfg.ssm_chunk,
        "time_step_min": cfg.ssm_dt_min,
        "time_step_max": cfg.ssm_dt_max,
        "time_step_floor": cfg.ssm_dt_floor,
        "n_routed_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.n_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_dim,
        "moe_shared_expert_intermediate_size": cfg.shared_expert_dim,
        "n_shared_experts": 1,
        "norm_topk_prob": cfg.moe_norm_topk,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "n_group": 1,
        "topk_group": 1,
    }
    if cfg.expert_share:
        out["share"] = {
            "router_num_experts": cfg.router_width,
            "rank": cfg.expert_offset // cfg.n_experts,
        }
    return out


_NEMO = "backbone.layers.{}."
# A layer kind's leaves: ours <- the name under `mixer.`, transposed ([out,
# in] -> [in, out]); the conv's taps and the experts are handled below.
_NEMO_MIXER = {
    "M": (
        ("ssm_in", "in_proj.weight", True),
        ("ssm_conv_b", "conv1d.bias", False),
        ("ssm_A_log", "A_log", False),
        ("ssm_D", "D", False),
        ("ssm_dt_bias", "dt_bias", False),
        ("ssm_norm", "norm.weight", False),
        ("ssm_out", "out_proj.weight", True),
    ),
    "E": (
        ("router", "gate.weight", True),
        ("router_bias", "gate.e_score_correction_bias", False),
        ("ws_u", "shared_experts.up_proj.weight", True),
        ("ws_d", "shared_experts.down_proj.weight", True),
    ),
    "*": (
        ("wq", "q_proj.weight", True),
        ("wk", "k_proj.weight", True),
        ("wv", "v_proj.weight", True),
        ("wo", "o_proj.weight", True),
    ),
}
_NEMO_EXPERT = (("wu", "up_proj"), ("wd", "down_proj"))


def _nemo_layers(cfg, kind):
    return [i for i, c in enumerate(cfg.layer_pattern) if c == kind]


def _nemotron_h_params_from_sd(cfg, sd, dtype=None):
    import jax.numpy as jnp

    dtype = dtype or cfg.dtype

    def get(name):
        if name not in sd:
            raise KeyError(f"missing tensor {name!r} in checkpoint")
        return np.asarray(sd[name], np.float32)

    def stack(layers, fn):
        return jnp.asarray(
            np.stack([fn(_NEMO.format(i) + "mixer.") for i in layers]), dtype)

    blocks = {
        "ln1": jnp.asarray(np.stack([
            get(_NEMO.format(i) + "norm.weight") for i in range(cfg.n_layers)
        ]), dtype)
    }
    for kind, leaves in _NEMO_MIXER.items():
        layers = _nemo_layers(cfg, kind)
        if not layers:
            continue
        for ours, theirs, t in leaves:
            blocks[ours] = stack(
                layers,
                lambda pre: get(pre + theirs).T if t else get(pre + theirs))
    if cfg.n_ssm_layers:  # [C, 1, K] -> [K, C], oldest tap first
        blocks["ssm_conv"] = stack(
            _nemo_layers(cfg, "M"),
            lambda pre: get(pre + "conv1d.weight")[:, 0, :].T)
    if cfg.n_moe_layers:
        for ours, theirs in _NEMO_EXPERT:
            blocks[ours] = stack(
                _nemo_layers(cfg, "E"),
                lambda pre: np.stack([
                    get(f"{pre}experts.{cfg.expert_offset + e}.{theirs}.weight").T
                    for e in range(cfg.n_experts)
                ]))
    return {
        "embed": jnp.asarray(get("backbone.embeddings.weight"), dtype),
        "blocks": blocks,
        "final_ln": jnp.asarray(get("backbone.norm_f.weight"), dtype),
        "lm_head": jnp.asarray(get("lm_head.weight").T, dtype),
    }


def _nemotron_h_params_to_sd(cfg, params):
    from areal_tpu.base.distributed import to_host

    def host(x):
        return to_host(x).astype(np.float32, copy=False)

    blocks = {n: host(w) for n, w in params["blocks"].items()}
    out = {
        "backbone.embeddings.weight": host(params["embed"]),
        "backbone.norm_f.weight": host(params["final_ln"]),
        "lm_head.weight": np.ascontiguousarray(host(params["lm_head"]).T),
    }
    for i in range(cfg.n_layers):
        out[_NEMO.format(i) + "norm.weight"] = blocks["ln1"][i]
    for kind, leaves in _NEMO_MIXER.items():
        for j, i in enumerate(_nemo_layers(cfg, kind)):
            pre = _NEMO.format(i) + "mixer."
            for ours, theirs, t in leaves:
                w = blocks[ours][j]
                out[pre + theirs] = np.ascontiguousarray(w.T) if t else w
            if kind == "M":
                out[pre + "conv1d.weight"] = np.ascontiguousarray(
                    blocks["ssm_conv"][j].T[:, None, :])
            if kind == "E":
                for ours, theirs in _NEMO_EXPERT:
                    for e in range(cfg.n_experts):
                        out[
                            f"{pre}experts.{cfg.expert_offset + e}."
                            f"{theirs}.weight"
                        ] = np.ascontiguousarray(blocks[ours][j, e].T)
    return out


register_hf_family(
    HFFamily(
        "nemotron_h",
        _nemotron_h_config_from_hf,
        _nemotron_h_config_to_hf,
        params_from_sd=_nemotron_h_params_from_sd,
        params_to_sd=_nemotron_h_params_to_sd,
    )
)


# ---------------- granitemoehybrid ----------------
# ibm-granite/granite-4.0-h-micro: `layer_types` gives every layer its
# mixer behind `input_layernorm` — "mamba", a Mamba-2 mixer (`mamba.*`, the
# tensors nemotron_h's have), or "attention" (q/k/v/o without bias, NO
# positional embedding at `position_embedding_type: nope`) — and a dense
# SwiGLU MLP behind `post_attention_layernorm`: `shared_mlp.output_linear(
# silu(gate) * up)` with [gate | up] = `shared_mlp.input_linear`.  Four
# multipliers: the embedding's, every residual add's, the attention
# scores' (in place of head_dim ** -0.5) and `logits_scaling`, which
# DIVIDES the logits; the head is tied to the embedding.  The MoE siblings
# (`num_local_experts` > 0: routed experts beside the shared MLP) are not
# modelled and are refused by name.  The tensor names are assumed (no
# network to re-read the module; `benchmark/configs/granite-4.0-h-micro-
# l10.json`, `assumed`).

_GRANITE_LAYER_TYPES = {"mamba": "M", "attention": "F"}
# The chunk of the program's SSD scan: the published `mamba_chunk_size`
# (256) sizes a kernel's tiling and nothing of the function; the program
# takes its own, at most this (`nemotron_h`'s 128: a [128, 128] decay
# block a head, half the memory of 256 at a packed row of 8,192 tokens).
_GRANITE_MAX_CHUNK = 128


def _granite_config_from_hf(hf: dict) -> ModelConfig:
    if hf.get("num_local_experts", 0):
        raise NotImplementedError(
            f"granitemoehybrid num_local_experts={hf['num_local_experts']}: "
            "routed experts beside the shared MLP (the MoE siblings of "
            "granite-4.0-h-micro) are not modelled"
        )
    for key, fine in (
        ("attention_bias", False), ("mamba_conv_bias", True),
        ("mamba_proj_bias", False), ("hidden_act", "silu"),
        ("normalization_function", "rmsnorm"), ("rope_scaling", None),
    ):
        if hf.get(key, fine) != fine:
            raise NotImplementedError(
                f"granitemoehybrid {key}={hf[key]!r} is not modelled"
            )
    positions = hf.get("position_embedding_type", "nope")
    if positions not in ("nope", "rope"):
        raise NotImplementedError(
            f"granitemoehybrid position_embedding_type={positions!r}"
        )
    types = hf["layer_types"]
    if len(types) != hf["num_hidden_layers"] or set(types) - set(
        _GRANITE_LAYER_TYPES
    ):
        raise ValueError(
            f"layer_types {types!r} is not {hf['num_hidden_layers']} of "
            f"{sorted(_GRANITE_LAYER_TYPES)}"
        )
    return ModelConfig(
        n_layers=hf["num_hidden_layers"],
        hidden_dim=hf["hidden_size"],
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf["hidden_size"] // hf["num_attention_heads"],
        intermediate_dim=hf["shared_intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 131072),
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        tied_embeddings=bool(hf.get("tie_word_embeddings", True)),
        pos_emb="none" if positions == "nope" else "rope",
        window_pattern="".join(_GRANITE_LAYER_TYPES[t] for t in types),
        ssm_n_heads=hf["mamba_n_heads"],
        ssm_head_dim=hf["mamba_d_head"],
        ssm_n_groups=hf.get("mamba_n_groups", 1),
        ssm_state_dim=hf["mamba_d_state"],
        ssm_conv_kernel=hf.get("mamba_d_conv", 4),
        ssm_chunk=min(hf.get("mamba_chunk_size", 256), _GRANITE_MAX_CHUNK),
        embedding_multiplier=float(hf.get("embedding_multiplier", 1.0)),
        residual_multiplier=float(hf.get("residual_multiplier", 1.0)),
        attention_multiplier=float(hf.get("attention_multiplier", 0.0)),
        logits_scaling=float(hf.get("logits_scaling", 1.0)),
    )


def _granite_config_to_hf(cfg: ModelConfig) -> dict:
    kinds = {v: k for k, v in _GRANITE_LAYER_TYPES.items()}
    return {
        "model_type": "granitemoehybrid",
        "architectures": ["GraniteMoeHybridForCausalLM"],
        "torch_dtype": "bfloat16",
        "num_hidden_layers": cfg.n_layers,
        "layer_types": [kinds[c] for c in cfg.window_pattern],
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.intermediate_dim,
        "shared_intermediate_size": cfg.intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_position_embeddings,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": None,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tied_embeddings,
        "position_embedding_type": (
            "nope" if cfg.pos_emb == "none" else "rope"),
        "attention_bias": False,
        "hidden_act": "silu",
        "normalization_function": "rmsnorm",
        "num_local_experts": 0,
        "num_experts_per_tok": 0,
        "mamba_n_heads": cfg.ssm_n_heads,
        "mamba_d_head": cfg.ssm_head_dim,
        "mamba_n_groups": cfg.ssm_n_groups,
        "mamba_d_state": cfg.ssm_state_dim,
        "mamba_d_conv": cfg.ssm_conv_kernel,
        "mamba_expand": cfg.ssm_inner_dim // cfg.hidden_dim,
        "mamba_chunk_size": cfg.ssm_chunk,
        "mamba_conv_bias": True,
        "mamba_proj_bias": False,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attn_scale,
        "logits_scaling": cfg.logits_scaling,
    }


_GRANITE = "model.layers.{}."
# A mixer's leaves: ours <- the name behind the layer's prefix, transposed
# ([out, in] -> [in, out]); the conv's taps and the MLP are handled below.
_GRANITE_MIXER = {
    "M": (
        ("ssm_in", "mamba.in_proj.weight", True),
        ("ssm_conv_b", "mamba.conv1d.bias", False),
        ("ssm_A_log", "mamba.A_log", False),
        ("ssm_D", "mamba.D", False),
        ("ssm_dt_bias", "mamba.dt_bias", False),
        ("ssm_norm", "mamba.norm.weight", False),
        ("ssm_out", "mamba.out_proj.weight", True),
    ),
    "F": (
        ("wq", "self_attn.q_proj.weight", True),
        ("wk", "self_attn.k_proj.weight", True),
        ("wv", "self_attn.v_proj.weight", True),
        ("wo", "self_attn.o_proj.weight", True),
    ),
}


def _granite_layers(cfg, kind):
    return [i for i, c in enumerate(cfg.window_pattern) if c == kind]


def _granite_params_from_sd(cfg, sd, dtype=None):
    import jax.numpy as jnp

    dtype = dtype or cfg.dtype
    f = cfg.intermediate_dim

    def get(name):
        if name not in sd:
            raise KeyError(f"missing tensor {name!r} in checkpoint")
        return np.asarray(sd[name], np.float32)

    def stack(layers, fn):
        return jnp.asarray(
            np.stack([fn(_GRANITE.format(i)) for i in layers]), dtype)

    every = range(cfg.n_layers)
    blocks = {
        "ln1": stack(every, lambda pre: get(pre + "input_layernorm.weight")),
        "ln2": stack(
            every, lambda pre: get(pre + "post_attention_layernorm.weight")),
        # input_linear [2F, D]: gate's rows, then up's.
        "wg": stack(every, lambda pre: get(
            pre + "shared_mlp.input_linear.weight")[:f].T),
        "wu": stack(every, lambda pre: get(
            pre + "shared_mlp.input_linear.weight")[f:].T),
        "wd": stack(every, lambda pre: get(
            pre + "shared_mlp.output_linear.weight").T),
    }
    for kind, leaves in _GRANITE_MIXER.items():
        layers = _granite_layers(cfg, kind)
        for ours, theirs, t in leaves if layers else ():
            blocks[ours] = stack(
                layers,
                lambda pre: get(pre + theirs).T if t else get(pre + theirs))
    if cfg.n_ssm_layers:  # [C, 1, K] -> [K, C], oldest tap first
        blocks["ssm_conv"] = stack(
            _granite_layers(cfg, "M"),
            lambda pre: get(pre + "mamba.conv1d.weight")[:, 0, :].T)
    params = {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), dtype),
        "blocks": blocks,
        "final_ln": jnp.asarray(get("model.norm.weight"), dtype),
    }
    if not cfg.tied_embeddings:
        params["lm_head"] = jnp.asarray(get("lm_head.weight").T, dtype)
    return params


def _granite_params_to_sd(cfg, params):
    from areal_tpu.base.distributed import to_host

    def host(x):
        return to_host(x).astype(np.float32, copy=False)

    blocks = {n: host(w) for n, w in params["blocks"].items()}
    out = {
        "model.embed_tokens.weight": host(params["embed"]),
        "model.norm.weight": host(params["final_ln"]),
    }
    if not cfg.tied_embeddings:
        out["lm_head.weight"] = np.ascontiguousarray(
            host(params["lm_head"]).T)
    for i in range(cfg.n_layers):
        pre = _GRANITE.format(i)
        out[pre + "input_layernorm.weight"] = blocks["ln1"][i]
        out[pre + "post_attention_layernorm.weight"] = blocks["ln2"][i]
        out[pre + "shared_mlp.input_linear.weight"] = np.ascontiguousarray(
            np.concatenate([blocks["wg"][i].T, blocks["wu"][i].T]))
        out[pre + "shared_mlp.output_linear.weight"] = np.ascontiguousarray(
            blocks["wd"][i].T)
    for kind, leaves in _GRANITE_MIXER.items():
        for j, i in enumerate(_granite_layers(cfg, kind)):
            pre = _GRANITE.format(i)
            for ours, theirs, t in leaves:
                w = blocks[ours][j]
                out[pre + theirs] = np.ascontiguousarray(w.T) if t else w
            if kind == "M":
                out[pre + "mamba.conv1d.weight"] = np.ascontiguousarray(
                    blocks["ssm_conv"][j].T[:, None, :])
    return out


register_hf_family(
    HFFamily(
        "granitemoehybrid",
        _granite_config_from_hf,
        _granite_config_to_hf,
        params_from_sd=_granite_params_from_sd,
        params_to_sd=_granite_params_to_sd,
    )
)


# ---------------- minicpm_sala ----------------
# openbmb/MiniCPM-SALA: `mixer_types` gives every layer its mixer behind
# `input_layernorm` — "minicpm4", softmax attention WITHOUT positions
# (`attn_use_rope: false`) over the blocks of keys a query selects
# (InfLLM-V2: `ops/block_sparse.py`; the selection's sizes are a
# `sparse_config` group, MiniCPM4's where the published file has none) with
# a per-head q/k RMSNorm and a sigmoid output gate (`o_gate`), or
# "lightning-attn", Lightning linear attention with rope (`models/
# lightning.py`) — and a dense SwiGLU MLP behind `post_attention_layernorm`.
# muP: the embedding times `scale_emb`, every residual add times
# `scale_depth / sqrt(mup_denominator)` (the PUBLISHED depth, 32: a cut in
# depth keeps it), the final hidden state divided by `hidden_size /
# dim_model_base` before the head — `logits_scaling`, the same product in
# another order.  The tensor names are assumed (no network to re-read the
# module; `benchmark/configs/minicpm-sala-l4-v8.json`, `assumed`).

_SALA_MIXERS = {"minicpm4": "B", "lightning-attn": "L"}
_SALA_SPARSE = {  # MiniCPM4 / MiniCPM4.1's published `sparse_config`
    "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
    "init_blocks": 1, "window_size": 2048, "dense_len": 8192,
}


def _sala_config_from_hf(hf: dict) -> ModelConfig:
    for key, fine in (
        ("attention_bias", False), ("hidden_act", "silu"),
        ("attn_use_rope", False), ("lightning_use_rope", True),
        ("qk_norm", True), ("use_output_gate", True),
        ("use_output_norm", True), ("attn_use_output_gate", True),
        ("lightning_scale", "1/sqrt(d)"),
    ):
        if hf.get(key, fine) != fine:
            raise NotImplementedError(
                f"minicpm_sala {key}={hf[key]!r} is not modelled"
            )
    types = hf["mixer_types"]
    if len(types) != hf["num_hidden_layers"] or set(types) - set(_SALA_MIXERS):
        raise ValueError(
            f"mixer_types {types!r} is not {hf['num_hidden_layers']} of "
            f"{sorted(_SALA_MIXERS)}"
        )
    if hf.get("lightning_nkv", hf["lightning_nh"]) != hf["lightning_nh"]:
        raise NotImplementedError(
            f"minicpm_sala lightning_nkv={hf['lightning_nkv']} of "
            f"lightning_nh={hf['lightning_nh']}: grouped Lightning heads "
            "are not modelled"
        )
    sparse = {**_SALA_SPARSE, **hf.get("sparse_config", {})}
    return ModelConfig(
        n_layers=hf["num_hidden_layers"],
        hidden_dim=hf["hidden_size"],
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get(
            "head_dim", hf["hidden_size"] // hf["num_attention_heads"]),
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 524288),
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        tied_embeddings=bool(hf.get("tie_word_embeddings", False)),
        pos_emb="none",  # the attention layers'; Lightning ropes itself
        qk_norm=True,
        qk_norm_per_head=True,
        attn_gate=True,
        window_pattern="".join(_SALA_MIXERS[t] for t in types),
        lightning_n_heads=hf["lightning_nh"],
        lightning_head_dim=hf["lightning_head_dim"],
        sparse_kernel_size=sparse["kernel_size"],
        sparse_kernel_stride=sparse["kernel_stride"],
        sparse_block_size=sparse["block_size"],
        sparse_topk=sparse["topk"],
        sparse_init_blocks=sparse["init_blocks"],
        sparse_window=sparse["window_size"],
        sparse_dense_len=sparse["dense_len"],
        embedding_multiplier=float(hf.get("scale_emb", 1.0)),
        residual_multiplier=float(
            hf.get("scale_depth", 1.0)
            / hf.get("mup_denominator", hf["num_hidden_layers"]) ** 0.5),
        logits_scaling=float(
            hf["hidden_size"] / hf.get("dim_model_base", hf["hidden_size"])),
    )


def _sala_config_to_hf(cfg: ModelConfig) -> dict:
    kinds = {v: k for k, v in _SALA_MIXERS.items()}
    base = 256  # dim_model_base: hidden_size / logits_scaling
    if cfg.logits_scaling != 1.0:
        base = round(cfg.hidden_dim / cfg.logits_scaling)
    # scale_depth / sqrt(mup_denominator) is ONE number here: written back
    # over the published denominator, 32.
    denominator = 32
    return {
        "model_type": "minicpm_sala",
        "architectures": ["MiniCPMSALAForCausalLM"],
        "torch_dtype": "bfloat16",
        "num_hidden_layers": cfg.n_layers,
        "mixer_types": [kinds[c] for c in cfg.window_pattern],
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "intermediate_size": cfg.intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_position_embeddings,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tied_embeddings,
        "attention_bias": False,
        "hidden_act": "silu",
        "attn_use_rope": False,
        "lightning_use_rope": True,
        "lightning_nh": cfg.lightning_n_heads,
        "lightning_nkv": cfg.lightning_n_heads,
        "lightning_head_dim": cfg.lightning_head_dim,
        "lightning_scale": "1/sqrt(d)",
        "qk_norm": True,
        "use_output_gate": True,
        "use_output_norm": True,
        "attn_use_output_gate": True,
        "scale_emb": cfg.embedding_multiplier,
        "scale_depth": cfg.residual_multiplier * denominator**0.5,
        # arealint: ignore[stats-keys] -- a published config key, no stat
        "mup_denominator": denominator,
        "dim_model_base": base,
        "sparse_config": {
            "kernel_size": cfg.sparse_kernel_size,
            "kernel_stride": cfg.sparse_kernel_stride,
            "block_size": cfg.sparse_block_size,
            "topk": cfg.sparse_topk,
            "init_blocks": cfg.sparse_init_blocks,
            "window_size": cfg.sparse_window,
            "dense_len": cfg.sparse_dense_len,
        },
    }


_SALA = "model.layers.{}."
# A mixer's leaves: ours <- the name behind the layer's prefix, transposed
# ([out, in] -> [in, out]) where a matrix.
_SALA_MIXER = {
    "B": (
        ("wq", "self_attn.q_proj.weight", True),
        ("wk", "self_attn.k_proj.weight", True),
        ("wv", "self_attn.v_proj.weight", True),
        ("wo", "self_attn.o_proj.weight", True),
        ("wqg", "self_attn.o_gate.weight", True),
        ("q_norm", "self_attn.q_norm.weight", False),
        ("k_norm", "self_attn.k_norm.weight", False),
    ),
    "L": (
        ("lt_wq", "self_attn.q_proj.weight", True),
        ("lt_wk", "self_attn.k_proj.weight", True),
        ("lt_wv", "self_attn.v_proj.weight", True),
        ("lt_wo", "self_attn.o_proj.weight", True),
        ("lt_wg", "self_attn.z_proj.weight", True),
        ("lt_q_norm", "self_attn.q_norm.weight", False),
        ("lt_k_norm", "self_attn.k_norm.weight", False),
        ("lt_norm", "self_attn.o_norm.weight", False),
    ),
}
_SALA_EVERY = (
    ("ln1", "input_layernorm.weight", False),
    ("ln2", "post_attention_layernorm.weight", False),
    ("wg", "mlp.gate_proj.weight", True),
    ("wu", "mlp.up_proj.weight", True),
    ("wd", "mlp.down_proj.weight", True),
)


def _sala_layers(cfg, kind):
    return [i for i, c in enumerate(cfg.window_pattern) if c == kind]


def _sala_params_from_sd(cfg, sd, dtype=None):
    import jax.numpy as jnp

    dtype = dtype or cfg.dtype

    def get(name):
        if name not in sd:
            raise KeyError(f"missing tensor {name!r} in checkpoint")
        return np.asarray(sd[name], np.float32)

    def stack(layers, theirs, t):
        return jnp.asarray(np.stack([
            get(_SALA.format(i) + theirs).T if t
            else get(_SALA.format(i) + theirs) for i in layers
        ]), dtype)

    blocks = {
        ours: stack(range(cfg.n_layers), theirs, t)
        for ours, theirs, t in _SALA_EVERY
    }
    for kind, leaves in _SALA_MIXER.items():
        layers = _sala_layers(cfg, kind)
        for ours, theirs, t in leaves if layers else ():
            blocks[ours] = stack(layers, theirs, t)
    params = {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), dtype),
        "blocks": blocks,
        "final_ln": jnp.asarray(get("model.norm.weight"), dtype),
    }
    if not cfg.tied_embeddings:
        params["lm_head"] = jnp.asarray(get("lm_head.weight").T, dtype)
    return params


def _sala_params_to_sd(cfg, params):
    from areal_tpu.base.distributed import to_host

    def host(x):
        return to_host(x).astype(np.float32, copy=False)

    def put(out, name, w, t):
        out[name] = np.ascontiguousarray(w.T) if t else w

    blocks = {n: host(w) for n, w in params["blocks"].items()}
    out = {
        "model.embed_tokens.weight": host(params["embed"]),
        "model.norm.weight": host(params["final_ln"]),
    }
    if not cfg.tied_embeddings:
        out["lm_head.weight"] = np.ascontiguousarray(
            host(params["lm_head"]).T)
    for i in range(cfg.n_layers):
        for ours, theirs, t in _SALA_EVERY:
            put(out, _SALA.format(i) + theirs, blocks[ours][i], t)
    for kind, leaves in _SALA_MIXER.items():
        for j, i in enumerate(_sala_layers(cfg, kind)):
            for ours, theirs, t in leaves:
                put(out, _SALA.format(i) + theirs, blocks[ours][j], t)
    return out


register_hf_family(
    HFFamily(
        "minicpm_sala",
        _sala_config_from_hf,
        _sala_config_to_hf,
        params_from_sd=_sala_params_from_sd,
        params_to_sd=_sala_params_to_sd,
    )
)


# ---------------- lfm2_moe ----------------
# LiquidAI/LFM2-8B-A1B: `layer_types` gives every layer its mixer behind
# `operator_norm` — "conv", the gated short convolution (`conv.in_proj` ->
# [B | C | u], a depthwise `conv.conv` of `conv_L_cache` taps without bias
# over B * u, `conv.out_proj` of C * that), or "full_attention" (q/k/v/
# `out_proj` without bias, an RMSNorm per head over q and k —
# `q_layernorm` / `k_layernorm` — before rope on rotated halves) — and an
# MLP behind `ffn_norm`: SwiGLU `feed_forward.w2(silu(w1 x) * w3 x)` in the
# first `num_dense_layers`, after them a mixture of such experts behind a
# sigmoid router (`feed_forward.gate`) whose top k are chosen by score +
# `feed_forward.expert_bias` and weighted by their scores over (their sum +
# 1e-6).  No shared expert; `model.embedding_norm` before a head tied to
# the embedding.  A `share` group cuts the model to one expert-parallel
# rank as for glm4_moe_lite: `num_experts` is then the number HELD here of
# `share.router_num_experts`.

_LFM2_LAYER_TYPES = {"conv": "C", "full_attention": "F"}


def _lfm2_moe_config_from_hf(hf: dict) -> ModelConfig:
    for key, fine in (("conv_bias", False), ("use_expert_bias", True)):
        if hf.get(key, fine) != fine:
            raise NotImplementedError(
                f"lfm2_moe {key}={hf[key]!r} is not modelled")
    n_layers = hf["num_hidden_layers"]
    types = hf["layer_types"]
    if len(types) != n_layers or set(types) - set(_LFM2_LAYER_TYPES):
        raise ValueError(
            f"lfm2_moe layer_types {types!r}: {n_layers} of "
            f"{sorted(_LFM2_LAYER_TYPES)} are wanted"
        )
    share = hf.get("share") or {}
    n_experts = hf["num_experts"]
    width = share.get("router_num_experts", n_experts)
    assumed = (hf.get("benchmark") or {}).get("assumed") or {}
    return ModelConfig(
        n_layers=n_layers,
        hidden_dim=hf["hidden_size"],
        n_q_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        head_dim=(
            hf.get("head_dim")
            or hf["hidden_size"] // hf["num_attention_heads"]
        ),
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 128000),
        rope_theta=float(hf.get("rope_theta", 1000000.0)),
        rms_norm_eps=hf.get("norm_eps", 1e-5),
        qk_norm=True,
        qk_norm_per_head=True,
        tied_embeddings=hf.get("tie_word_embeddings", True),
        first_k_dense=hf.get("num_dense_layers", 0),
        window_pattern="".join(_LFM2_LAYER_TYPES[t] for t in types),
        sconv_kernel=hf["conv_L_cache"],
        n_experts=n_experts,
        n_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_dim=hf["moe_intermediate_size"],
        moe_norm_topk=bool(hf.get("norm_topk_prob", True)),
        moe_norm_topk_eps=1e-6,
        moe_aux_loss_coef=0.0,
        moe_score_func="sigmoid",
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
        router_bias_init_std=float(assumed.get("router_bias_init_std", 0.0)),
        n_router_experts=0 if width == n_experts else width,
        expert_offset=share.get("rank", 0) * n_experts,
    )


def _lfm2_moe_config_to_hf(cfg: ModelConfig) -> dict:
    names = {c: t for t, c in _LFM2_LAYER_TYPES.items()}
    out = {
        "model_type": "lfm2_moe",
        "architectures": ["Lfm2MoeForCausalLM"],
        "torch_dtype": "bfloat16",
        "num_hidden_layers": cfg.n_layers,
        "layer_types": [names[c] for c in cfg.window_pattern],
        "hidden_size": cfg.hidden_dim,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.max_position_embeddings,
        "rope_theta": cfg.rope_theta,
        "norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tied_embeddings,
        "conv_L_cache": cfg.sconv_kernel,
        "conv_bias": False,
        "num_dense_layers": cfg.first_k_dense,
        "num_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.n_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_dim,
        "norm_topk_prob": cfg.moe_norm_topk,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "use_expert_bias": True,
    }
    if cfg.head_dim * cfg.n_q_heads != cfg.hidden_dim:
        out["head_dim"] = cfg.head_dim
    if cfg.expert_share:
        out["share"] = {
            "router_num_experts": cfg.router_width,
            "rank": cfg.expert_offset // cfg.n_experts,
        }
    return out


_LFM2 = "model.layers.{}."
# ours <- the HF name under the layer, and how: "T" transposed ([out, in]
# -> [in, out]), "" as it is, "taps" the conv's [C, 1, K] -> [K, C].
_LFM2_NORMS = (
    ("ln1", "operator_norm.weight", ""), ("ln2", "ffn_norm.weight", ""),
)
_LFM2_MIXER = {
    "C": (
        ("sc_in", "conv.in_proj.weight", "T"),
        ("sc_conv", "conv.conv.weight", "taps"),
        ("sc_out", "conv.out_proj.weight", "T"),
    ),
    "F": (
        ("wq", "self_attn.q_proj.weight", "T"),
        ("wk", "self_attn.k_proj.weight", "T"),
        ("wv", "self_attn.v_proj.weight", "T"),
        ("wo", "self_attn.out_proj.weight", "T"),
        ("q_norm", "self_attn.q_layernorm.weight", ""),
        ("k_norm", "self_attn.k_layernorm.weight", ""),
    ),
}
_LFM2_DENSE = (
    ("wg", "feed_forward.w1.weight", "T"),
    ("wu", "feed_forward.w3.weight", "T"),
    ("wd", "feed_forward.w2.weight", "T"),
)
_LFM2_SPARSE = (
    ("router", "feed_forward.gate.weight", "T"),
    ("router_bias", "feed_forward.expert_bias", ""),
)
_LFM2_EXPERT = (("wg", "w1"), ("wu", "w3"), ("wd", "w2"))
_LFM2_READ = {
    "": lambda w: w, "T": lambda w: w.T, "taps": lambda w: w[:, 0, :].T,
}
_LFM2_WRITE = {
    "": lambda w: w, "T": lambda w: w.T, "taps": lambda w: w.T[:, None, :],
}


def _lfm2_tensors(cfg):
    """Every tensor of the layers as (our leaf under `blocks`, its place in
    the leaf's stack — (layer,) or (layer, expert) —, the HF name, how):
    the leading dense layers' under `dense_*`, a mixer's leaf stacked over
    its group's layers with that mixer, in layer order."""
    k = cfg.first_k_dense
    for pre, layers, mlp in (
        (DENSE_PREFIX, range(k), _LFM2_DENSE),
        ("", range(k, cfg.n_layers), _LFM2_SPARSE),
    ):
        seen = {c: 0 for c in _LFM2_MIXER}
        for j, i in enumerate(layers):
            base, kind = _LFM2.format(i), cfg.window_pattern[i]
            for ours, theirs, how in _LFM2_NORMS + mlp:
                yield pre + ours, (j,), base + theirs, how
            for ours, theirs, how in _LFM2_MIXER[kind]:
                yield pre + ours, (seen[kind],), base + theirs, how
            seen[kind] += 1
            if pre:
                continue
            for ours, theirs in _LFM2_EXPERT:
                for e in range(cfg.n_experts):
                    yield ours, (j, e), (
                        f"{base}feed_forward.experts."
                        f"{cfg.expert_offset + e}.{theirs}.weight"
                    ), "T"


def _lfm2_moe_params_from_sd(cfg, sd, dtype=None):
    import jax.numpy as jnp

    dtype = dtype or cfg.dtype

    def get(name):
        if name not in sd:
            raise KeyError(f"missing tensor {name!r} in checkpoint")
        return np.asarray(sd[name], np.float32)

    parts: Dict[str, dict] = {}
    for leaf, at, name, how in _lfm2_tensors(cfg):
        parts.setdefault(leaf, {})[at] = _LFM2_READ[how](get(name))

    def stack(by_place):  # {(layer,) or (layer, expert): tensor}
        lead = tuple(np.max(list(by_place), axis=0) + 1)
        flat = np.stack([by_place[at] for at in np.ndindex(*lead)])
        return flat.reshape(*lead, *flat.shape[1:])

    params = {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), dtype),
        "blocks": {
            leaf: jnp.asarray(stack(p), dtype) for leaf, p in parts.items()
        },
        "final_ln": jnp.asarray(get("model.embedding_norm.weight"), dtype),
    }
    if not cfg.tied_embeddings:
        params["lm_head"] = jnp.asarray(get("lm_head.weight").T, dtype)
    return params


def _lfm2_moe_params_to_sd(cfg, params):
    from areal_tpu.base.distributed import to_host

    def host(x):
        return to_host(x).astype(np.float32, copy=False)

    blocks = {n: host(w) for n, w in params["blocks"].items()}
    out = {
        "model.embed_tokens.weight": host(params["embed"]),
        "model.embedding_norm.weight": host(params["final_ln"]),
    }
    if not cfg.tied_embeddings:
        out["lm_head.weight"] = np.ascontiguousarray(host(params["lm_head"]).T)
    for leaf, at, name, how in _lfm2_tensors(cfg):
        out[name] = np.ascontiguousarray(_LFM2_WRITE[how](blocks[leaf][at]))
    return out


register_hf_family(
    HFFamily(
        "lfm2_moe",
        _lfm2_moe_config_from_hf,
        _lfm2_moe_config_to_hf,
        params_from_sd=_lfm2_moe_params_from_sd,
        params_to_sd=_lfm2_moe_params_to_sd,
    )
)


# ---------------- gpt2 ----------------
# Different lineage: learned positions, LayerNorm with bias, fused c_attn,
# plain (non-gated) gelu MLP, biases everywhere, Conv1D weights stored
# [in, out] — which matches this codebase's convention directly.


def _gpt2_config_from_hf(hf: dict) -> ModelConfig:
    d = hf["n_embd"]
    heads = hf["n_head"]
    return ModelConfig(
        n_layers=hf["n_layer"],
        hidden_dim=d,
        n_q_heads=heads,
        n_kv_heads=heads,
        head_dim=d // heads,
        intermediate_dim=hf.get("n_inner") or 4 * d,
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("n_positions", 1024),
        rms_norm_eps=hf.get("layer_norm_epsilon", 1e-5),
        qkv_bias=True,
        tied_embeddings=True,
        hidden_act="gelu_tanh",  # gelu_new
        norm_type="layernorm",
        pos_emb="learned",
        mlp_gated=False,
        proj_bias=True,
    )


def _gpt2_config_to_hf(cfg: ModelConfig) -> dict:
    return {
        "model_type": "gpt2",
        "n_layer": cfg.n_layers,
        "n_embd": cfg.hidden_dim,
        "n_head": cfg.n_q_heads,
        "n_inner": cfg.intermediate_dim,
        "vocab_size": cfg.vocab_size,
        "n_positions": cfg.max_position_embeddings,
        "n_ctx": cfg.max_position_embeddings,
        "layer_norm_epsilon": cfg.rms_norm_eps,
        "activation_function": "gelu_new",
        "tie_word_embeddings": True,
        "torch_dtype": "float32",
        "architectures": ["GPT2LMHeadModel"],
    }


def _gpt2_params_from_sd(cfg, sd, dtype=None):
    import jax.numpy as jnp

    dtype = dtype or cfg.dtype
    L, D = cfg.n_layers, cfg.hidden_dim

    def get(name):
        key = name if name in sd else "transformer." + name
        return np.asarray(sd[key])

    def stack(fmt):
        return np.stack([get(fmt.format(i)) for i in range(L)], axis=0)

    c_attn_w = stack("h.{}.attn.c_attn.weight")  # [L, D, 3D] (Conv1D: in,out)
    c_attn_b = stack("h.{}.attn.c_attn.bias")  # [L, 3D]
    blocks = {
        "ln1": stack("h.{}.ln_1.weight"),
        "ln1_b": stack("h.{}.ln_1.bias"),
        "wq": c_attn_w[:, :, :D],
        "wk": c_attn_w[:, :, D : 2 * D],
        "wv": c_attn_w[:, :, 2 * D :],
        "bq": c_attn_b[:, :D],
        "bk": c_attn_b[:, D : 2 * D],
        "bv": c_attn_b[:, 2 * D :],
        "wo": stack("h.{}.attn.c_proj.weight"),
        "bo": stack("h.{}.attn.c_proj.bias"),
        "ln2": stack("h.{}.ln_2.weight"),
        "ln2_b": stack("h.{}.ln_2.bias"),
        "wg": stack("h.{}.mlp.c_fc.weight"),
        "bfc": stack("h.{}.mlp.c_fc.bias"),
        "wd": stack("h.{}.mlp.c_proj.weight"),
        "bproj": stack("h.{}.mlp.c_proj.bias"),
    }
    params = {
        "embed": get("wte.weight"),
        "pos_embed": get("wpe.weight"),
        "blocks": blocks,
        "final_ln": get("ln_f.weight"),
        "final_ln_b": get("ln_f.bias"),
    }
    params = jax.tree.map(lambda x: jnp.asarray(x, dtype=dtype), params)
    if cfg.is_critic:
        params["value_head"] = jnp.zeros((D, 1), dtype=dtype)
    return params


def _gpt2_params_to_sd(cfg, params):
    from areal_tpu.base.distributed import to_host

    host = jax.tree.map(
        lambda x: to_host(x).astype(np.float32, copy=False), params
    )
    blocks = host["blocks"]
    out = {
        "wte.weight": host["embed"],
        "wpe.weight": host["pos_embed"],
        "ln_f.weight": host["final_ln"],
        "ln_f.bias": host["final_ln_b"],
    }
    for i in range(cfg.n_layers):
        pre = f"h.{i}."
        out[pre + "ln_1.weight"] = blocks["ln1"][i]
        out[pre + "ln_1.bias"] = blocks["ln1_b"][i]
        out[pre + "attn.c_attn.weight"] = np.ascontiguousarray(
            np.concatenate(
                [blocks["wq"][i], blocks["wk"][i], blocks["wv"][i]], axis=1
            )
        )
        out[pre + "attn.c_attn.bias"] = np.ascontiguousarray(
            np.concatenate(
                [blocks["bq"][i], blocks["bk"][i], blocks["bv"][i]]
            )
        )
        out[pre + "attn.c_proj.weight"] = blocks["wo"][i]
        out[pre + "attn.c_proj.bias"] = blocks["bo"][i]
        out[pre + "ln_2.weight"] = blocks["ln2"][i]
        out[pre + "ln_2.bias"] = blocks["ln2_b"][i]
        out[pre + "mlp.c_fc.weight"] = blocks["wg"][i]
        out[pre + "mlp.c_fc.bias"] = blocks["bfc"][i]
        out[pre + "mlp.c_proj.weight"] = blocks["wd"][i]
        out[pre + "mlp.c_proj.bias"] = blocks["bproj"][i]
    return {k: np.ascontiguousarray(v) for k, v in out.items()}


register_hf_family(
    HFFamily(
        "gpt2",
        _gpt2_config_from_hf,
        _gpt2_config_to_hf,
        params_from_sd=_gpt2_params_from_sd,
        params_to_sd=_gpt2_params_to_sd,
    )
)


# ---------------- dots3_note ----------------
# dots-studio/dots3-note-prev, the language model (the vision and audio
# towers and the multi-token-prediction module are not modelled): latent
# attention in two geometries by `layer_types` — "full_attention" layers at
# the deepseek_v3 keys behind a token indexer (`index_*`), and
# "sliding_attention" layers at the `swa_*` keys over `sliding_window_size`
# keys — a headwise output gate on both, glm4_moe_lite's sparse MLP.
#
# A `share` group cuts the model to ONE rank of a deployment: experts as
# for glm4_moe_lite (`n_routed_experts` HELD of `share.router_num_experts`),
# and HEADS: `num_attention_heads` / `swa_num_attention_heads` are the heads
# held of `share.published_num_attention_heads` /
# `share.published_swa_num_attention_heads`, as a tensor-parallel rank
# holds them; the low-rank down-projections, their norms and the indexer
# are whole.
#
# The catalog row gives the config and no tensor names: the state-dict
# converters refuse by name.


def _dots3_pattern(hf: dict) -> str:
    kinds = {"full_attention": "F", "sliding_attention": "S"}
    types = hf["layer_types"]
    if set(types) - set(kinds) or len(types) != hf["num_hidden_layers"]:
        raise NotImplementedError(
            f"dots3_note layer_types {types!r}: num_hidden_layers entries "
            "of full_attention / sliding_attention")
    return "".join(kinds[t] for t in types)


def _dots3_note_config_from_hf(hf: dict) -> ModelConfig:
    for key, fine in (
        ("rope_scaling", None), ("attention_bias", False),
        ("hidden_act", "silu"), ("topk_method", "noaux_tc"),
        ("scoring_func", "sigmoid"), ("moe_layer_freq", 1),
        ("attention_gate_type", "headwise"),
        ("swa_attention_gate_type", "headwise"),
    ):
        if hf.get(key, fine) != fine:
            raise NotImplementedError(
                f"dots3_note {key}={hf[key]!r} is not modelled")
    share = hf.get("share") or {}
    n_experts = hf["n_routed_experts"]
    width = share.get("router_num_experts", n_experts)
    heads, swa_heads = hf["num_attention_heads"], hf["swa_num_attention_heads"]
    published = share.get("published_num_attention_heads", heads)
    if published % heads or (
        share.get("published_swa_num_attention_heads", swa_heads) * heads
        != published * swa_heads
    ):
        raise ValueError(
            "dots3_note share: both geometries' held heads are the same "
            "whole share of the published ones")
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    assumed = (hf.get("benchmark") or {}).get("assumed") or {}
    return ModelConfig(
        n_layers=hf["num_hidden_layers"],
        hidden_dim=hf["hidden_size"],
        n_q_heads=heads,
        n_kv_heads=heads,
        head_dim=nope + rope,
        intermediate_dim=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        max_position_embeddings=hf.get("max_position_embeddings", 524288),
        rope_theta=float(hf.get("rope_theta", 80000000.0)),
        window_rope_theta=float(hf.get("swa_rope_theta", 50000.0)),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        tied_embeddings=hf.get("tie_word_embeddings", False),
        q_lora_rank=hf["q_lora_rank"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=nope,
        qk_rope_head_dim=rope,
        v_head_dim=hf["v_head_dim"],
        window_pattern=_dots3_pattern(hf),
        attn_window=hf["sliding_window_size"],
        swa_n_heads=swa_heads,
        swa_q_lora_rank=hf["swa_q_lora_rank"],
        swa_kv_lora_rank=hf["swa_kv_lora_rank"],
        swa_qk_nope_head_dim=hf["swa_qk_nope_head_dim"],
        swa_qk_rope_head_dim=hf["swa_qk_rope_head_dim"],
        swa_v_head_dim=hf["swa_v_head_dim"],
        index_n_heads=hf.get("index_n_heads", 0),
        index_head_dim=hf.get("index_head_dim", 0),
        index_topk=hf.get("index_topk", 0),
        attn_gate_headwise=True,
        latent_rescale=bool(hf.get("apply_mla_qkv_lora_rescale", False)),
        head_share=published // heads,
        first_k_dense=hf.get("first_k_dense_replace", 0),
        n_experts=n_experts,
        n_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_dim=hf["moe_intermediate_size"],
        moe_norm_topk=bool(hf.get("norm_topk_prob", True)),
        moe_aux_loss_coef=0.0,
        moe_score_func="sigmoid",
        moe_routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
        router_bias_init_std=float(assumed.get("router_bias_init_std", 0.0)),
        shared_expert_dim=(
            hf.get("n_shared_experts", 0) * hf["moe_intermediate_size"]),
        shared_expert_gated=False,
        n_router_experts=0 if width == n_experts else width,
        expert_offset=share.get("rank", 0) * n_experts,
    )


def _dots3_note_config_to_hf(cfg: ModelConfig) -> dict:
    out = _llama_like_config_to_hf(cfg, "dots3_note")
    out.pop("head_dim")  # not a key of this family: nope + rope
    kinds = {"F": "full_attention", "S": "sliding_attention"}
    out.update(
        architectures=["Dots3NoteForConditionalGeneration"],
        hidden_act=cfg.hidden_act,
        attention_bias=False,
        rope_scaling=None,
        apply_mla_qkv_lora_rescale=cfg.latent_rescale,
        attention_gate_type="headwise",
        swa_attention_gate_type="headwise",
        layer_types=[kinds[c] for c in cfg.window_pattern],
        sliding_window_size=cfg.attn_window,
        q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim,
        swa_num_attention_heads=cfg.swa_n_heads,
        swa_num_key_value_heads=cfg.swa_n_heads,
        swa_q_lora_rank=cfg.swa_q_lora_rank,
        swa_kv_lora_rank=cfg.swa_kv_lora_rank,
        swa_qk_nope_head_dim=cfg.swa_qk_nope_head_dim,
        swa_qk_rope_head_dim=cfg.swa_qk_rope_head_dim,
        swa_v_head_dim=cfg.swa_v_head_dim,
        swa_rope_theta=cfg.window_rope_theta,
        index_n_heads=cfg.index_n_heads,
        index_head_dim=cfg.index_head_dim,
        index_topk=cfg.index_topk,
        first_k_dense_replace=cfg.first_k_dense,
        moe_layer_freq=1,
        n_routed_experts=cfg.n_experts,
        num_experts_per_tok=cfg.n_experts_per_tok,
        moe_intermediate_size=cfg.moe_intermediate_dim,
        n_shared_experts=cfg.shared_expert_dim // cfg.moe_intermediate_dim,
        norm_topk_prob=cfg.moe_norm_topk,
        routed_scaling_factor=cfg.moe_routed_scale,
        scoring_func="sigmoid",
        topk_method="noaux_tc",
    )
    if cfg.expert_share or cfg.head_share != 1:
        out["share"] = {
            "router_num_experts": cfg.router_width,
            "rank": cfg.expert_offset // cfg.n_experts,
            "published_num_attention_heads": cfg.n_q_heads * cfg.head_share,
            "published_swa_num_attention_heads": (
                cfg.swa_n_heads * cfg.head_share),
        }
    return out


def _dots3_note_no_tensors(*_, **__):
    raise NotImplementedError(
        "dots3_note: the published tensor names are not in the catalog row "
        "this family was written from; weights are drawn (`init_params`), "
        "no checkpoint is read or written")


register_hf_family(
    HFFamily(
        "dots3_note",
        _dots3_note_config_from_hf,
        _dots3_note_config_to_hf,
        params_from_sd=_dots3_note_no_tensors,
        params_to_sd=_dots3_note_no_tensors,
    )
)


def infer_model_type(cfg: ModelConfig) -> str:
    """Best-fit HF family for a ModelConfig — the save path's dispatcher
    when the caller didn't record where the weights came from."""
    if cfg.norm_type == "layernorm":
        return "gpt2"
    if cfg.block_length:
        return "sdar_moe"
    if cfg.is_hybrid:
        return "olmo_hybrid" if cfg.branch_norm == "output" else "qwen3_next"
    if cfg.n_sparse_layers or cfg.n_lightning_layers:
        return "minicpm_sala"
    if cfg.is_pattern:
        return "nemotron_h"
    if cfg.is_latent:
        return "dots3_note" if cfg.window_pattern else "glm4_moe_lite"
    if cfg.n_sconv_layers:
        return "lfm2_moe"
    if cfg.n_ssm_layers:  # Mamba-2 mixers in two-branch layers
        return "granitemoehybrid"
    if cfg.window_pattern or cfg.rope_yarn_factor:
        return "mellum" if cfg.is_moe else "mistral"
    if cfg.is_moe:
        return "olmoe" if cfg.qk_norm else "mixtral"
    if cfg.rms_norm_offset:
        return "gemma"
    if cfg.qkv_bias:
        return "qwen2"
    return "llama"


# ---------------- checkpoint IO ----------------


def load_hf_config(path: str) -> dict:
    with open(os.path.join(path, "config.json")) as f:
        return json.load(f)


def load_model_config(path: str, is_critic: bool = False) -> ModelConfig:
    """Config-only load (no weights) — e.g. remote-generator workers that
    hold no local params."""
    hf_cfg = load_hf_config(path)
    cfg = HF_FAMILIES[hf_cfg["model_type"]].config_from_hf(hf_cfg)
    return cfg.as_critic() if is_critic else cfg


def load_hf_checkpoint(
    path: str, is_critic: bool = False, dtype=None
) -> "tuple[ModelConfig, Dict[str, Any]]":
    """Load an HF checkpoint dir (safetensors or torch .bin shards)."""
    hf_cfg = load_hf_config(path)
    family = HF_FAMILIES[hf_cfg["model_type"]]
    cfg = family.config_from_hf(hf_cfg)
    if is_critic:
        cfg = cfg.as_critic()
    sd: Dict[str, np.ndarray] = {}
    st_files = sorted(
        f for f in os.listdir(path) if f.endswith(".safetensors")
    )
    if st_files:
        from safetensors.numpy import load_file

        for f in st_files:
            sd.update(load_file(os.path.join(path, f)))
    else:
        import torch

        bins = sorted(f for f in os.listdir(path) if f.endswith(".bin"))
        if not bins:
            raise FileNotFoundError(f"no safetensors/bin shards in {path}")
        for f in bins:
            t = torch.load(
                os.path.join(path, f), map_location="cpu", weights_only=True
            )
            sd.update({k: v.float().numpy() for k, v in t.items()})
    params = family.params_from_sd(cfg, sd, dtype=dtype)
    logger.info(f"loaded HF checkpoint from {path} ({hf_cfg['model_type']})")
    return cfg, params


def save_hf_checkpoint(
    path: str,
    cfg: ModelConfig,
    params: Dict[str, Any],
    model_type: str = "qwen2",
    tokenizer=None,
    max_shard_bytes: int = 5 * 1024**3,
) -> None:
    """Write an HF-format checkpoint dir (safetensors + config.json) so the
    reference's eval tooling / vLLM / SGLang can consume our outputs.
    State dicts over `max_shard_bytes` split into the standard
    model-XXXXX-of-YYYYY.safetensors shards + index json (the layout
    transformers/vLLM expect for large models)."""
    from areal_tpu.base.distributed import is_primary

    # Host-gathering a process-spanning param tree is collective: every
    # group member computes the state dict, only jax process 0 writes.
    sd = HF_FAMILIES[model_type].params_to_sd(cfg, params)
    if not is_primary():
        return
    os.makedirs(path, exist_ok=True)
    from safetensors.numpy import save_file

    total = sum(v.nbytes for v in sd.values())
    if total <= max_shard_bytes:
        save_file(sd, os.path.join(path, "model.safetensors"))
    else:
        shards: list = [[]]
        size = 0
        for k in sd:
            if size + sd[k].nbytes > max_shard_bytes and shards[-1]:
                shards.append([])
                size = 0
            shards[-1].append(k)
            size += sd[k].nbytes
        n = len(shards)
        weight_map = {}
        for i, keys in enumerate(shards):
            fname = f"model-{i + 1:05d}-of-{n:05d}.safetensors"
            save_file({k: sd[k] for k in keys}, os.path.join(path, fname))
            weight_map.update({k: fname for k in keys})
        with open(
            os.path.join(path, "model.safetensors.index.json"), "w"
        ) as f:
            json.dump(
                {
                    "metadata": {"total_size": total},
                    "weight_map": weight_map,
                },
                f,
                indent=2,
            )
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(HF_FAMILIES[model_type].config_to_hf(cfg), f, indent=2)
    if tokenizer is not None and hasattr(tokenizer, "save_pretrained"):
        tokenizer.save_pretrained(path)
