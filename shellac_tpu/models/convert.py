"""HuggingFace Llama-family checkpoint conversion.

Lets a user bring existing torch weights (Llama/Mistral-style decoders:
GQA + SwiGLU + RMSNorm + NeoX-form RoPE) into shellac_tpu's stacked
pytree layout:

  - torch `nn.Linear` stores (out, in); we store (in, out) → transpose.
  - HF RMSNorm weight `W` multiplies directly; ours applies `(1 + s)` →
    s = W - 1 (so a zero-init tree is the identity scale).
  - per-layer tensors stack along a leading `layers` axis to match the
    `lax.scan` forward.

Conversion is numerics-exact: the parity test compares our forward
against `transformers`' LlamaForCausalLM logits on the same weights.

Works from a live HF model, a state_dict, or a directory saved with
`save_pretrained` (loaded locally — no network).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional

import jax.numpy as jnp
import numpy as np

from shellac_tpu.config import ModelConfig


def config_from_hf(hf_cfg) -> ModelConfig:
    """ModelConfig from a Llama/Mistral/Mixtral transformers config.

    Mistral's sliding window maps to attn_window; Mixtral's experts map
    to a dropless MoEConfig (exact top-k computation, no capacity drops)
    with every layer MoE.
    """
    from shellac_tpu.config import MoEConfig

    n_heads = hf_cfg.num_attention_heads
    head_dim = getattr(hf_cfg, "head_dim", None) or (
        hf_cfg.hidden_size // n_heads
    )
    is_gemma = getattr(hf_cfg, "model_type", "") == "gemma"
    if getattr(hf_cfg, "model_type", "") in ("deepseek_v2", "deepseek_v3"):
        return _deepseek_config(hf_cfg)
    if getattr(hf_cfg, "model_type", "") == "gemma2":
        return _gemma2_config(hf_cfg)
    if getattr(hf_cfg, "model_type", "") in ("gemma3_text", "gemma3"):
        return _gemma3_config(hf_cfg)
    if getattr(hf_cfg, "model_type", "") == "gpt_oss":
        return _gptoss_config(hf_cfg)
    if getattr(hf_cfg, "model_type", "") == "evabyte":
        return _evabyte_config(hf_cfg)
    is_ouro = getattr(hf_cfg, "model_type", "") == "ouro"
    moe = None
    if getattr(hf_cfg, "num_local_experts", None):
        moe = MoEConfig(
            num_experts=hf_cfg.num_local_experts,
            num_experts_per_token=hf_cfg.num_experts_per_tok,
            router_aux_loss_weight=getattr(
                hf_cfg, "router_aux_loss_coef", 0.01
            ),
            dropless=True,
        )
    if getattr(hf_cfg, "model_type", "") == "phi3":
        if getattr(hf_cfg, "partial_rotary_factor", 1.0) != 1.0:
            raise NotImplementedError(
                "phi3 partial_rotary_factor != 1 is not supported"
            )
    # Keye-VL-2.0's text model carries the Qwen3-MoE block's keys plus
    # `sa_config`, the indexer of its learned sparse attention.
    is_keye = getattr(hf_cfg, "model_type", "") == "KeyeVL2"
    is_qwen3 = is_keye or getattr(hf_cfg, "model_type", "") in (
        "qwen3", "qwen3_moe")
    if is_keye or getattr(hf_cfg, "model_type", "") == "qwen3_moe":
        if getattr(hf_cfg, "mlp_only_layers", None):
            raise NotImplementedError(
                "qwen3_moe with mlp_only_layers is a mixed layout we "
                "cannot represent uniformly"
            )
        if getattr(hf_cfg, "decoder_sparse_step", 1) != 1:
            raise NotImplementedError(
                "qwen3_moe decoder_sparse_step != 1 is not representable"
            )
        moe = MoEConfig(
            num_experts=hf_cfg.num_experts,
            num_experts_per_token=hf_cfg.num_experts_per_tok,
            d_ff_expert=hf_cfg.moe_intermediate_size,
            # HF Qwen3MoeConfig defaults norm_topk_prob to False.
            norm_topk_prob=bool(getattr(hf_cfg, "norm_topk_prob", False)),
            router_aux_loss_weight=getattr(
                hf_cfg, "router_aux_loss_coef", 0.01
            ),
            dropless=True,
        )
    return ModelConfig(
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=hf_cfg.num_hidden_layers,
        n_heads=n_heads,
        n_kv_heads=getattr(hf_cfg, "num_key_value_heads", None) or n_heads,
        head_dim=head_dim,
        d_ff=hf_cfg.intermediate_size,
        max_seq_len=hf_cfg.max_position_embeddings,
        rope_theta=getattr(hf_cfg, "rope_theta", 10000.0),
        norm_eps=hf_cfg.rms_norm_eps,
        tie_embeddings=bool(getattr(hf_cfg, "tie_word_embeddings", False)),
        attn_window=_hf_attn_window(hf_cfg),
        moe=moe,
        # Gemma: tanh-GeGLU MLP, sqrt(d)-scaled embeddings, and its
        # RMSNorm is already the (1+w) form ours uses.
        activation="geglu" if is_gemma else "swiglu",
        embed_scale=is_gemma,
        # Qwen2 puts biases on q/k/v (detected from the config flag
        # where present, else model type); Qwen3 dropped the biases in
        # favour of per-head-dim q/k RMSNorm.
        attn_bias=bool(
            getattr(hf_cfg, "attention_bias", False)
            or getattr(hf_cfg, "model_type", "") == "qwen2"
        ),
        qk_norm=is_qwen3,
        dsa=_dsa_from_hf(hf_cfg) if is_keye else None,
        # Ouro: sandwich norms and the whole stack run total_ut_steps
        # times a token (LoopConfig).
        post_norms=is_ouro,
        loop=_loop_from_hf(hf_cfg) if is_ouro else None,
        # Long-context checkpoints: yarn/llama3 convert exactly; any
        # other rope_scaling type fails loudly.
        **_rope_from_hf(
            getattr(hf_cfg, "rope_scaling", None),
            hf_cfg.max_position_embeddings,
        ),
    ).validate()


def _loop_from_hf(hf_cfg):
    """LoopConfig from Ouro's `total_ut_steps` / `early_exit_threshold`.
    Every layer attends every earlier position: a `layer_types` entry
    other than full_attention has no looped form here."""
    from shellac_tpu.config import LoopConfig

    kinds = set(getattr(hf_cfg, "layer_types", None) or ())
    if kinds - {"full_attention"}:
        raise NotImplementedError(
            f"ouro layer_types {sorted(kinds - {'full_attention'})}: a "
            "looped stack with windowed layers is not supported (rings "
            "hold one window a layer, not one a pass)"
        )
    return LoopConfig(
        steps=int(hf_cfg.total_ut_steps),
        exit_threshold=float(getattr(hf_cfg, "early_exit_threshold", 1.0)),
    )


#: Ouro's names for the four norms of a layer, by ours. Gemma-2's
#: sandwich uses other names for the same places (below).
_OURO_NORMS = {
    "attn_norm": "input_layernorm",
    "post_attn_norm": "input_layernorm_2",
    "mlp_norm": "post_attention_layernorm",
    "post_mlp_norm": "post_attention_layernorm_2",
}


def _dsa_from_hf(hf_cfg):
    """DSAConfig from Keye-VL-2.0's `sa_config`. Token-only input: the
    three position axes of M-RoPE (`rope_scaling.mrope_section`) are
    equal for text, which makes it the ordinary rope; image tokens'
    three-axis positions refuse where they would enter
    (ops/rope.py::mrope_token_positions)."""
    from shellac_tpu.config import DSAConfig

    sa = hf_cfg.sa_config
    sa = sa if isinstance(sa, dict) else vars(sa)
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise NotImplementedError(
            "sa_config.indexer_num_kv_heads != 1: the indexer caches one "
            "index key a token"
        )
    return DSAConfig(index_heads=sa["indexer_num_heads"],
                     index_dim=sa["indexer_head_dim"], topk=sa["topk"])


def _evabyte_config(hf_cfg) -> ModelConfig:
    """EvaByte: EVA attention (an exact window plus one pooled row a
    chunk), multi-byte prediction heads, a float32 residual stream, and
    RMSNorm in the (1 + w) form ours uses (norm_add_unit_offset, as
    Gemma: see _norm_offset)."""
    from shellac_tpu.config import EvaConfig

    if getattr(hf_cfg, "attention_class", "eva") != "eva":
        raise NotImplementedError(
            f"evabyte attention_class={hf_cfg.attention_class!r}; have 'eva'"
        )
    if not getattr(hf_cfg, "norm_add_unit_offset", False):
        raise NotImplementedError(
            "evabyte without norm_add_unit_offset is not representable "
            "(our RMSNorm multiplies by 1 + w)"
        )
    if getattr(hf_cfg, "rope_scaling", None) is not None:
        raise NotImplementedError("evabyte with rope_scaling")
    if getattr(hf_cfg, "attention_bias", False):
        raise NotImplementedError("evabyte with attention_bias")
    n_heads = hf_cfg.num_attention_heads
    return ModelConfig(
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=hf_cfg.num_hidden_layers,
        n_heads=n_heads,
        n_kv_heads=getattr(hf_cfg, "num_key_value_heads", None) or n_heads,
        d_ff=hf_cfg.intermediate_size,
        max_seq_len=hf_cfg.max_position_embeddings,
        rope_theta=float(getattr(hf_cfg, "rope_theta", 10000.0)),
        norm_eps=hf_cfg.rms_norm_eps,
        tie_embeddings=bool(getattr(hf_cfg, "tie_word_embeddings", False)),
        eva=EvaConfig(window=hf_cfg.window_size, chunk=hf_cfg.chunk_size),
        n_pred_heads=getattr(hf_cfg, "num_pred_heads", 1),
        fp32_residual=bool(getattr(hf_cfg, "fp32_skip_add", False)),
    ).validate()


def _pattern_from_layer_types(layer_types) -> tuple:
    """Minimal-period attn_pattern from an HF layer_types list.

    HF stores one entry per layer ("sliding_attention"/"full_attention");
    our config stores the repeating period. Unknown kinds fail loudly.
    """
    kinds = []
    for t in layer_types:
        if t == "sliding_attention":
            kinds.append("window")
        elif t == "full_attention":
            kinds.append("full")
        else:
            raise NotImplementedError(f"unknown layer_type {t!r}")
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n)):
            return tuple(kinds[:p])
    return tuple(kinds)


def _gemma2_config(hf_cfg) -> ModelConfig:
    """Gemma-2 config mapping: alternating local/global attention
    (layer_types -> attn_pattern), tanh soft-capping on attention scores
    and final logits, sandwich norms (post_norms), a query_pre_attn_scalar
    score scale, GeGLU, and sqrt(d)-scaled embeddings."""
    n_layers = hf_cfg.num_hidden_layers
    layer_types = getattr(hf_cfg, "layer_types", None) or [
        # Older configs predate layer_types; HF's fallback is sliding
        # attention on even layer indices.
        "sliding_attention" if i % 2 == 0 else "full_attention"
        for i in range(n_layers)
    ]
    pattern = _pattern_from_layer_types(layer_types)
    windowed = "window" in pattern
    if set(pattern) == {"window"}:
        pattern = None  # uniform window: the plain attn_window covers it
    elif set(pattern) == {"full"}:
        pattern, windowed = None, False
    qpas = getattr(hf_cfg, "query_pre_attn_scalar", None)
    return ModelConfig(
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=n_layers,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=getattr(hf_cfg, "num_key_value_heads", None)
        or hf_cfg.num_attention_heads,
        head_dim=getattr(hf_cfg, "head_dim", None)
        or hf_cfg.hidden_size // hf_cfg.num_attention_heads,
        d_ff=hf_cfg.intermediate_size,
        max_seq_len=hf_cfg.max_position_embeddings,
        rope_theta=getattr(hf_cfg, "rope_theta", 10000.0),
        norm_eps=hf_cfg.rms_norm_eps,
        tie_embeddings=bool(getattr(hf_cfg, "tie_word_embeddings", True)),
        attn_window=int(hf_cfg.sliding_window) if windowed else None,
        attn_pattern=pattern,
        attn_softcap=getattr(hf_cfg, "attn_logit_softcapping", None),
        logit_softcap=getattr(hf_cfg, "final_logit_softcapping", None),
        attn_scale=None if qpas is None else float(qpas) ** -0.5,
        post_norms=True,
        activation="geglu",
        embed_scale=True,
    ).validate()


def _gemma3_config(hf_cfg) -> ModelConfig:
    """Gemma-3 (text) config mapping: the Gemma-2 block (sandwich norms,
    GeGLU, scaled embeddings, patterned local/global attention) minus
    the softcaps, plus Qwen3-style per-head-dim q/k RMSNorm and DUAL
    rope — local layers rope with rope_local_base_freq unscaled, global
    layers with rope_theta and the checkpoint's (linear) rope scaling.
    """
    if getattr(hf_cfg, "model_type", "") == "gemma3":
        # Multimodal wrapper config: the text tower's config nests under
        # text_config; vision conversion is out of scope.
        inner = getattr(hf_cfg, "text_config", None)
        if inner is None:
            raise NotImplementedError(
                "gemma3 config without a text_config (vision-only?)"
            )
        hf_cfg = inner
    n_layers = hf_cfg.num_hidden_layers
    swp = getattr(hf_cfg, "sliding_window_pattern", None) or 6
    layer_types = getattr(hf_cfg, "layer_types", None) or [
        # Older configs predate layer_types: every swp-th layer is
        # global (sliding_window_pattern, default 6).
        "full_attention" if (i + 1) % swp == 0 else "sliding_attention"
        for i in range(n_layers)
    ]
    pattern = _pattern_from_layer_types(layer_types)
    windowed = "window" in pattern
    uniform = len(set(pattern)) == 1
    if uniform:
        pattern = None
    rope_kw = _rope_from_hf(
        getattr(hf_cfg, "rope_scaling", None),
        hf_cfg.max_position_embeddings,
    )
    rope_linear = rope_kw.pop("rope_linear", None)
    if rope_kw:
        raise NotImplementedError(
            f"gemma3 with {sorted(rope_kw)} rope scaling (have: linear)"
        )
    qpas = getattr(hf_cfg, "query_pre_attn_scalar", None)
    local_theta = getattr(hf_cfg, "rope_local_base_freq", None)
    rope_theta = getattr(hf_cfg, "rope_theta", 1000000.0)
    if uniform and windowed and local_theta is not None:
        # Every layer is sliding: the local frequency base IS the rope,
        # and the global-layer scaling never applies.
        rope_theta, rope_linear = float(local_theta), None
    return ModelConfig(
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=n_layers,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=getattr(hf_cfg, "num_key_value_heads", None)
        or hf_cfg.num_attention_heads,
        head_dim=getattr(hf_cfg, "head_dim", None)
        or hf_cfg.hidden_size // hf_cfg.num_attention_heads,
        d_ff=hf_cfg.intermediate_size,
        max_seq_len=hf_cfg.max_position_embeddings,
        rope_theta=rope_theta,
        rope_linear=rope_linear,
        rope_local_theta=(float(local_theta)
                          if windowed and not uniform
                          and local_theta is not None else None),
        norm_eps=hf_cfg.rms_norm_eps,
        tie_embeddings=bool(getattr(hf_cfg, "tie_word_embeddings", True)),
        attn_window=int(hf_cfg.sliding_window) if windowed else None,
        attn_pattern=pattern,
        attn_scale=None if qpas is None else float(qpas) ** -0.5,
        qk_norm=True,
        post_norms=True,
        activation="geglu",
        embed_scale=True,
    ).validate()


def _gptoss_config(hf_cfg) -> ModelConfig:
    """GPT-OSS config mapping: alternating sliding/full attention with
    learned per-head SINK logits, q/k/v/o biases, yarn rope (truncate
    False), and an all-MoE stack with the softmax-after-top-k gate,
    biased experts, the clamped (up+1)*glu activation, and narrow
    per-expert FFNs."""
    from shellac_tpu.config import MoEConfig

    n_layers = hf_cfg.num_hidden_layers
    layer_types = getattr(hf_cfg, "layer_types", None) or [
        "sliding_attention" if i % 2 == 0 else "full_attention"
        for i in range(n_layers)
    ]
    pattern = _pattern_from_layer_types(layer_types)
    windowed = "window" in pattern
    if set(pattern) == {"window"}:
        pattern = None
    elif set(pattern) == {"full"}:
        pattern, windowed = None, False
    moe = MoEConfig(
        num_experts=hf_cfg.num_local_experts,
        num_experts_per_token=hf_cfg.num_experts_per_tok,
        d_ff_expert=hf_cfg.intermediate_size,
        scoring="softmax_topk",
        expert_bias=True,
        # HF hardcodes these in GptOssExperts (no config fields).
        gate_limit=7.0,
        expert_act="gptoss",
        router_aux_loss_weight=getattr(hf_cfg, "router_aux_loss_coef",
                                       0.9),
        dropless=True,
    )
    return ModelConfig(
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=n_layers,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=getattr(hf_cfg, "num_key_value_heads", None)
        or hf_cfg.num_attention_heads,
        head_dim=getattr(hf_cfg, "head_dim", None)
        or hf_cfg.hidden_size // hf_cfg.num_attention_heads,
        d_ff=hf_cfg.intermediate_size,
        max_seq_len=hf_cfg.max_position_embeddings,
        rope_theta=getattr(hf_cfg, "rope_theta", 150000.0),
        norm_eps=hf_cfg.rms_norm_eps,
        tie_embeddings=bool(getattr(hf_cfg, "tie_word_embeddings", False)),
        attn_window=int(hf_cfg.sliding_window) if windowed else None,
        attn_pattern=pattern,
        attn_bias=bool(getattr(hf_cfg, "attention_bias", True)),
        attn_out_bias=bool(getattr(hf_cfg, "attention_bias", True)),
        attn_sink=True,
        moe=moe,
        **_rope_from_hf(
            getattr(hf_cfg, "rope_scaling", None),
            hf_cfg.max_position_embeddings,
        ),
    ).validate()


def _deepseek_config(hf_cfg) -> ModelConfig:
    """DeepSeek-V2/V3 (MLA) config mapping.

    Supports the full architecture: MLA attention (with optional yarn
    rope), the first-k-dense layer layout, and both MoE gates — V2's
    softmax scoring with greedy or group-limited top-k and
    un-normalized scaled probabilities, and V3's sigmoid scoring with
    selection-only correction biases, top-2-sum group ranking, and
    normalized weights — plus narrow per-expert FFNs
    (moe_intermediate_size) and shared experts. Unrepresentable knobs
    (per-layer MoE frequency, non-yarn rope scaling, attention biases,
    gating declared different from each HF reference) fail loudly
    rather than converting approximately.
    """
    from shellac_tpu.config import MLAConfig, MoEConfig

    n_layers = hf_cfg.num_hidden_layers
    first_k = getattr(hf_cfg, "first_k_dense_replace", n_layers)
    is_v3 = getattr(hf_cfg, "model_type", "") == "deepseek_v3"
    moe = None
    if first_k < n_layers and getattr(hf_cfg, "n_routed_experts", None):
        if getattr(hf_cfg, "moe_layer_freq", 1) != 1:
            raise NotImplementedError(
                "moe_layer_freq != 1 is not representable by the "
                "first_k_dense layout"
            )
        if first_k == 0:
            raise NotImplementedError(
                "all-MoE DeepSeek (first_k_dense_replace=0) conversion "
                "is not wired; every published checkpoint keeps >= 1 "
                "dense layer"
            )
        common = dict(
            num_experts=hf_cfg.n_routed_experts,
            num_experts_per_token=hf_cfg.num_experts_per_tok,
            d_ff_expert=hf_cfg.moe_intermediate_size,
            num_shared_experts=getattr(hf_cfg, "n_shared_experts", 0) or 0,
            routed_scaling_factor=float(
                getattr(hf_cfg, "routed_scaling_factor", 1.0)
            ),
            dropless=True,
        )
        if is_v3:
            # V3 gate: sigmoid scores, bias-corrected top-2-sum group
            # selection, normalized combine weights. If the checkpoint's
            # config DECLARES different gating (remote-code variants
            # carry these fields), refuse rather than convert wrong.
            declared = getattr(hf_cfg, "scoring_func", "sigmoid")
            if declared != "sigmoid":
                raise NotImplementedError(
                    f"deepseek_v3 with scoring_func={declared!r} "
                    "(the HF reference gate is sigmoid)"
                )
            declared_tm = getattr(hf_cfg, "topk_method", "noaux_tc")
            if declared_tm != "noaux_tc":
                raise NotImplementedError(
                    f"deepseek_v3 with topk_method={declared_tm!r} "
                    "(the HF reference gate is noaux_tc)"
                )
            moe = MoEConfig(
                scoring="sigmoid",
                norm_topk_prob=bool(getattr(hf_cfg, "norm_topk_prob", True)),
                n_group=getattr(hf_cfg, "n_group", 1) or 1,
                topk_group=getattr(hf_cfg, "topk_group", 1) or 1,
                **common,
            )
        else:
            if getattr(hf_cfg, "scoring_func", "softmax") != "softmax":
                raise NotImplementedError(
                    f"DeepSeek-V2 scoring_func="
                    f"{hf_cfg.scoring_func!r} (have: softmax)"
                )
            if getattr(hf_cfg, "topk_method", "greedy") not in (
                "greedy", "group_limited_greedy",
            ):
                raise NotImplementedError(
                    f"DeepSeek topk_method={hf_cfg.topk_method!r}"
                )
            grouped = hf_cfg.topk_method == "group_limited_greedy"
            moe = MoEConfig(
                # HF's DeepseekV2 gate NEVER renormalizes the kept top-k
                # probabilities (the config flag is unused in its
                # forward), so matching HF's actual compute means False
                # regardless of what the checkpoint's config claims.
                norm_topk_prob=False,
                n_group=(getattr(hf_cfg, "n_group", 1) or 1)
                if grouped else 1,
                topk_group=(getattr(hf_cfg, "topk_group", 1) or 1)
                if grouped else 1,
                **common,
            )
    elif first_k < n_layers:
        raise NotImplementedError(
            "first_k_dense_replace set but n_routed_experts missing"
        )
    if getattr(hf_cfg, "attention_bias", False):
        raise NotImplementedError(
            "DeepSeek attention_bias=True is not supported; converting "
            "would silently drop the bias tensors"
        )
    return ModelConfig(
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=hf_cfg.num_hidden_layers,
        n_heads=hf_cfg.num_attention_heads,
        d_ff=hf_cfg.intermediate_size,
        max_seq_len=hf_cfg.max_position_embeddings,
        rope_theta=getattr(hf_cfg, "rope_theta", 10000.0),
        norm_eps=hf_cfg.rms_norm_eps,
        tie_embeddings=bool(getattr(hf_cfg, "tie_word_embeddings", False)),
        mla=MLAConfig(
            kv_lora_rank=hf_cfg.kv_lora_rank,
            q_lora_rank=getattr(hf_cfg, "q_lora_rank", None),
            qk_nope_head_dim=hf_cfg.qk_nope_head_dim,
            qk_rope_head_dim=hf_cfg.qk_rope_head_dim,
            v_head_dim=hf_cfg.v_head_dim,
        ),
        moe=moe,
        first_k_dense=first_k if moe is not None else 0,
        **_rope_from_hf(
            getattr(hf_cfg, "rope_scaling", None),
            hf_cfg.max_position_embeddings,
        ),
    ).validate()


def _rope_from_hf(rs, max_pos) -> dict:
    """ModelConfig rope-scaling kwargs from an HF rope_scaling dict.

    yarn (DeepSeek/Qwen long-context) and llama3 (Llama-3.1 family)
    convert exactly; other scaling types fail loudly.
    """
    if not rs:
        return {}
    from shellac_tpu.config import Llama3RopeConfig, YarnConfig

    kind = rs.get("rope_type", rs.get("type"))
    if kind in ("linear", "default"):
        # Classic position interpolation: every inverse frequency
        # divides by the factor ("default" means no change).
        if kind == "default" or float(rs.get("factor", 1.0)) == 1.0:
            return {}
        return {"rope_linear": float(rs["factor"])}
    if kind == "llama3":
        if not rs.get("original_max_position_embeddings"):
            # Required: falling back to the post-scaling max would shift
            # both wavelength bands by the factor — silent divergence.
            raise ValueError(
                "llama3 rope_scaling requires "
                "original_max_position_embeddings"
            )
        return {"rope_llama3": Llama3RopeConfig(
            factor=rs["factor"],
            low_freq_factor=rs["low_freq_factor"],
            high_freq_factor=rs["high_freq_factor"],
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"
            ],
        )}
    if kind != "yarn":
        raise NotImplementedError(
            f"rope_scaling type {kind!r} is not supported "
            "(have: linear, yarn, llama3)"
        )
    return {"rope_yarn": YarnConfig(
        factor=rs["factor"],
        original_max_position_embeddings=rs.get(
            "original_max_position_embeddings"
        ) or max_pos,
        beta_fast=rs.get("beta_fast") or 32.0,
        beta_slow=rs.get("beta_slow") or 1.0,
        mscale=rs.get("mscale"),
        mscale_all_dim=rs.get("mscale_all_dim"),
        attention_factor=rs.get("attention_factor"),
        truncate=rs.get("truncate", True),
    )}


def _hf_attn_window(hf_cfg) -> Optional[int]:
    """Sliding-window size, honoring the flags HF actually checks.

    Qwen2 configs routinely ship sliding_window set but
    use_sliding_window=False — HF ignores the window there, so we must
    too. Per-layer windowing (max_window_layers < n_layers with SWA
    enabled) has no uniform-window equivalent; refuse rather than
    silently diverge.
    """
    window = getattr(hf_cfg, "sliding_window", None)
    if window is None or not getattr(hf_cfg, "use_sliding_window", True):
        return None
    # HF semantics: the first max_window_layers layers run FULL
    # attention; only layers beyond them use SWA. So mwl >= n_layers
    # means no layer is windowed, mwl == 0 means all are, and anything
    # in between is per-layer mixing we cannot represent uniformly.
    mwl = getattr(hf_cfg, "max_window_layers", None)
    if mwl is None or mwl == 0:
        return int(window)
    if mwl >= hf_cfg.num_hidden_layers:
        return None
    raise ValueError(
        f"per-layer sliding window (first max_window_layers={mwl} of "
        f"n_layers={hf_cfg.num_hidden_layers} full, rest windowed) is "
        "not representable as a uniform attn_window; refusing to convert"
    )


def _norm_offset(hf_cfg) -> float:
    """What to add to HF norm weights to get our (1+s) convention.

    Llama/Mistral/Mixtral RMSNorm multiplies by w directly -> s = w - 1.
    The Gemma family stores (1 + w) semantics natively -> s = w.
    """
    gemma_family = ("gemma", "gemma2", "gemma3", "gemma3_text")
    unit_offset = (getattr(hf_cfg, "model_type", "") in gemma_family
                   or getattr(hf_cfg, "norm_add_unit_offset", False))
    return 0.0 if unit_offset else -1.0


def _to_np(t) -> np.ndarray:
    if hasattr(t, "detach"):  # torch tensor
        return t.detach().to("cpu").float().numpy()
    return np.asarray(t, np.float32)


_ATTN_MAP = {
    # ours: (hf suffix, transpose?)
    "wq": ("self_attn.q_proj.weight", True),
    "wk": ("self_attn.k_proj.weight", True),
    "wv": ("self_attn.v_proj.weight", True),
    "wo": ("self_attn.o_proj.weight", True),
}

_DENSE_MLP_MAP = {
    "w_gate": ("mlp.gate_proj.weight", True),
    "w_up": ("mlp.up_proj.weight", True),
    "w_down": ("mlp.down_proj.weight", True),
}

# Mixtral experts: w1 = gate, w3 = up, w2 = down.
_EXPERT_MAP = {
    "w_gate": "w1",
    "w_up": "w3",
    "w_down": "w2",
}

# Qwen3-MoE (and DeepSeek) experts keep the dense projection names.
_QWEN3_EXPERT_MAP = {
    "w_gate": "gate_proj",
    "w_up": "up_proj",
    "w_down": "down_proj",
}

# Qwen2-style attention biases (vectors, no transpose).
_BIAS_MAP = {
    "bq": "self_attn.q_proj.bias",
    "bk": "self_attn.k_proj.bias",
    "bv": "self_attn.v_proj.bias",
}


def _collect_mla_layer(layers, m, get, base, norm_offset) -> None:
    """One DeepSeek (MLA) layer's attention weights into the stacks.

    kv_b_proj is one (H*(nope+v), kv_rank) matrix in HF; we split it
    into the key expansion `wkv_b_k` (kv_rank, H, nope) and value
    expansion `wkv_b_v` (kv_rank, H, v) that the absorbed decode
    contracts separately (models/transformer._mla_attention).
    """
    a = base + "self_attn."
    layers["wkv_a"].append(get(a + "kv_a_proj_with_mqa.weight").T)
    layers["kv_a_norm"].append(
        get(a + "kv_a_layernorm.weight") + norm_offset
    )
    kv_b = get(a + "kv_b_proj.weight").T  # (kv_rank, H*(nope+v))
    kv_b = kv_b.reshape(
        m.kv_lora_rank, -1, m.qk_nope_head_dim + m.v_head_dim
    )
    layers["wkv_b_k"].append(kv_b[..., : m.qk_nope_head_dim])
    layers["wkv_b_v"].append(kv_b[..., m.qk_nope_head_dim:])
    layers["wo"].append(get(a + "o_proj.weight").T)
    if m.q_lora_rank is None:
        layers["wq"].append(get(a + "q_proj.weight").T)
    else:
        layers["wq_a"].append(get(a + "q_a_proj.weight").T)
        layers["q_a_norm"].append(
            get(a + "q_a_layernorm.weight") + norm_offset
        )
        layers["wq_b"].append(get(a + "q_b_proj.weight").T)


def params_from_state_dict(
    state_dict: Mapping[str, Any], cfg: ModelConfig, dtype=None,
    norm_offset: float = -1.0, moe_naming: str = "auto",
) -> Dict[str, Any]:
    """Convert an HF Llama-family state_dict to a shellac_tpu pytree.

    norm_offset is added to HF norm weights (-1.0 for Llama-convention
    RMSNorm, 0.0 for Gemma; see _norm_offset).
    """
    sd = dict(state_dict)
    # Accept both bare and "model."-prefixed keys.
    prefix = "model." if any(k.startswith("model.") for k in sd) else ""
    pdt = dtype or cfg.params_dtype

    def get(name):
        key = f"{prefix}{name}"
        if key not in sd:
            raise KeyError(
                f"missing weight {key!r}; is this a Llama-family checkpoint?"
            )
        return _to_np(sd[key])

    if cfg.first_k_dense:
        return _first_k_params(cfg, get, sd, pdt, norm_offset)
    moe = cfg.moe is not None
    if moe and moe_naming == "auto":
        # Probe the keys: Mixtral ships block_sparse_moe.*, Qwen3-MoE
        # keeps the dense projection names under mlp.experts.*, GPT-OSS
        # fuses all experts into single stacked tensors.
        if f"{prefix}layers.0.mlp.experts.gate_up_proj" in sd:
            moe_naming = "gpt_oss"
        elif f"{prefix}layers.0.mlp.experts.0.gate_proj.weight" in sd:
            moe_naming = "qwen3_moe"
        else:
            moe_naming = "mixtral"
    if moe and cfg.moe_every > 1:
        raise NotImplementedError(
            "interleaved dense/MoE stacks (moe_every > 1) have no HF "
            "(Mixtral) checkpoint layout to convert from"
        )
    mlp_keys = (["w_router"] + list(_EXPERT_MAP) if moe
                else list(_DENSE_MLP_MAP))
    if moe and cfg.moe.scoring in ("sigmoid", "softmax_topk"):
        mlp_keys += ["b_router"]
    if moe and cfg.moe.expert_bias:
        mlp_keys += ["b_gate", "b_up", "b_down"]
    bias_keys = list(_BIAS_MAP) if cfg.attn_bias else []
    if cfg.attn_out_bias:
        bias_keys += ["bo"]
    if cfg.attn_sink:
        bias_keys += ["sinks"]
    if cfg.mla is not None:
        attn_keys = ["wkv_a", "kv_a_norm", "wkv_b_k", "wkv_b_v", "wo"]
        attn_keys += (["wq"] if cfg.mla.q_lora_rank is None
                      else ["wq_a", "q_a_norm", "wq_b"])
    else:
        attn_keys = list(_ATTN_MAP)
        if cfg.qk_norm:
            attn_keys += ["q_norm", "k_norm"]
    norm_keys = ["attn_norm", "mlp_norm"]
    if cfg.post_norms:
        norm_keys += ["post_attn_norm", "post_mlp_norm"]
    layers: Dict[str, list] = {
        k: []
        for k in [*attn_keys, *bias_keys, *mlp_keys, *norm_keys]
    }
    # Phi3 fuses q/k/v into one qkv_proj and gate/up into gate_up_proj;
    # detect from the keys and split on conversion.
    fused_qkv = f"{prefix}layers.0.self_attn.qkv_proj.weight" in sd
    for i in range(cfg.n_layers):
        base = f"layers.{i}."
        if cfg.mla is not None:
            _collect_mla_layer(layers, cfg.mla, get, base, norm_offset)
        elif fused_qkv:
            w = get(base + "self_attn.qkv_proj.weight").T  # (d, q+2kv)
            qd = cfg.n_heads * cfg.dim_per_head
            kvd = cfg.kv_heads * cfg.dim_per_head
            layers["wq"].append(w[:, :qd])
            layers["wk"].append(w[:, qd:qd + kvd])
            layers["wv"].append(w[:, qd + kvd:])
            layers["wo"].append(get(base + "self_attn.o_proj.weight").T)
        else:
            for ours, (theirs, transpose) in _ATTN_MAP.items():
                w = get(base + theirs)
                layers[ours].append(w.T if transpose else w)
            if cfg.qk_norm:
                layers["q_norm"].append(
                    get(base + "self_attn.q_norm.weight") + norm_offset
                )
                layers["k_norm"].append(
                    get(base + "self_attn.k_norm.weight") + norm_offset
                )
        for ours, theirs in (_BIAS_MAP.items() if cfg.attn_bias else ()):
            layers[ours].append(get(base + theirs))
        if cfg.attn_out_bias:
            layers["bo"].append(get(base + "self_attn.o_proj.bias"))
        if cfg.attn_sink:
            layers["sinks"].append(get(base + "self_attn.sinks"))
        if moe:
            if moe_naming == "gpt_oss":
                layers["w_router"].append(
                    get(base + "mlp.router.weight").T
                )
                layers["b_router"].append(get(base + "mlp.router.bias"))
                # Fused stacked experts: gate_up (E, D, 2F) INTERLEAVES
                # gate and up on the last dim; down is (E, F, D).
                gu = get(base + "mlp.experts.gate_up_proj")
                gub = get(base + "mlp.experts.gate_up_proj_bias")
                layers["w_gate"].append(gu[..., 0::2])
                layers["w_up"].append(gu[..., 1::2])
                layers["b_gate"].append(gub[..., 0::2])
                layers["b_up"].append(gub[..., 1::2])
                layers["w_down"].append(get(base + "mlp.experts.down_proj"))
                layers["b_down"].append(
                    get(base + "mlp.experts.down_proj_bias")
                )
            elif moe_naming == "qwen3_moe":
                layers["w_router"].append(get(base + "mlp.gate.weight").T)
                for ours, proj in _QWEN3_EXPERT_MAP.items():
                    layers[ours].append(np.stack([
                        get(base + f"mlp.experts.{j}.{proj}.weight").T
                        for j in range(cfg.moe.num_experts)
                    ]))
            else:
                layers["w_router"].append(
                    get(base + "block_sparse_moe.gate.weight").T
                )
                for ours, theirs in _EXPERT_MAP.items():
                    experts = [
                        get(
                            base
                            + f"block_sparse_moe.experts.{j}.{theirs}.weight"
                        ).T
                        for j in range(cfg.moe.num_experts)
                    ]
                    layers[ours].append(np.stack(experts))
        elif fused_qkv:
            gu = get(base + "mlp.gate_up_proj.weight").T  # (d, 2f)
            f = gu.shape[1] // 2
            layers["w_gate"].append(gu[:, :f])
            layers["w_up"].append(gu[:, f:])
            layers["w_down"].append(get(base + "mlp.down_proj.weight").T)
        else:
            for ours, (theirs, transpose) in _DENSE_MLP_MAP.items():
                w = get(base + theirs)
                layers[ours].append(w.T if transpose else w)
        if cfg.loop is not None:
            for ours, theirs in _OURO_NORMS.items():
                layers[ours].append(
                    get(base + theirs + ".weight") + norm_offset
                )
            continue
        layers["attn_norm"].append(
            get(base + "input_layernorm.weight") + norm_offset
        )
        if cfg.post_norms:
            # Gemma-2 sandwich norms: HF's post_attention_layernorm is
            # the attention OUTPUT norm (our post_attn_norm); the MLP
            # pre-norm is pre_feedforward_layernorm.
            layers["post_attn_norm"].append(
                get(base + "post_attention_layernorm.weight") + norm_offset
            )
            layers["mlp_norm"].append(
                get(base + "pre_feedforward_layernorm.weight") + norm_offset
            )
            layers["post_mlp_norm"].append(
                get(base + "post_feedforward_layernorm.weight") + norm_offset
            )
        else:
            layers["mlp_norm"].append(
                get(base + "post_attention_layernorm.weight") + norm_offset
            )

    params: Dict[str, Any] = {
        "embed": jnp.asarray(get("embed_tokens.weight"), pdt),
        "layers": {
            k: jnp.asarray(np.stack(v), pdt) for k, v in layers.items()
        },
        "final_norm": jnp.asarray(get("norm.weight") + norm_offset, pdt),
    }
    if not cfg.tie_embeddings:
        lm_head = sd.get("lm_head.weight")
        if lm_head is None:
            raise KeyError("untied config but no lm_head.weight in state_dict")
        params["lm_head"] = jnp.asarray(_to_np(lm_head).T, pdt)
    if cfg.loop is not None:
        params["loop_gate"] = {
            "w": jnp.asarray(get("early_exit_gate.weight").reshape(-1), pdt),
            "b": jnp.asarray(get("early_exit_gate.bias").reshape(()), pdt),
        }
    return params


def _first_k_params(cfg, get, sd, pdt, norm_offset):
    """DeepSeek first-k-dense checkpoint -> two-stack layer tree.

    Dense prefix layers carry plain MLPs (mlp.gate_proj...); MoE layers
    carry the router (mlp.gate.weight, stored (E, D) in HF), narrow
    per-expert FFNs (mlp.experts.{j}...), and optional shared experts
    (mlp.shared_experts...). Attention is MLA on every layer.
    """
    m = cfg.mla
    if m is None:
        raise NotImplementedError(
            "first_k_dense conversion is wired for MLA (DeepSeek) "
            "checkpoints only"
        )

    def collect(layer_range, moe_layer):
        from collections import defaultdict

        stacks: Dict[str, list] = defaultdict(list)
        put = lambda key, val: stacks[key].append(val)  # noqa: E731

        for i in layer_range:
            base = f"layers.{i}."
            _collect_mla_layer(stacks, m, get, base, norm_offset)
            put("attn_norm",
                get(base + "input_layernorm.weight") + norm_offset)
            put("mlp_norm",
                get(base + "post_attention_layernorm.weight") + norm_offset)
            if not moe_layer:
                for ours, (theirs, _) in _DENSE_MLP_MAP.items():
                    put(ours, get(base + theirs).T)
            else:
                put("w_router", get(base + "mlp.gate.weight").T)  # (D, E)
                if cfg.moe.scoring == "sigmoid":
                    put("b_router",
                        get(base + "mlp.gate.e_score_correction_bias"))
                for ours, proj in _QWEN3_EXPERT_MAP.items():
                    put(ours, np.stack([
                        get(base + f"mlp.experts.{j}.{proj}.weight").T
                        for j in range(cfg.moe.num_experts)
                    ]))
                if cfg.moe.num_shared_experts > 0:
                    for ours, proj in (
                        ("w_gate_shared", "gate_proj"),
                        ("w_up_shared", "up_proj"),
                        ("w_down_shared", "down_proj"),
                    ):
                        put(ours, get(
                            base + f"mlp.shared_experts.{proj}.weight"
                        ).T)
        return {k: jnp.asarray(np.stack(v), pdt) for k, v in stacks.items()}

    kk = cfg.first_k_dense
    params: Dict[str, Any] = {
        "embed": jnp.asarray(get("embed_tokens.weight"), pdt),
        "layers": {
            "dense": collect(range(kk), False),
            "moe": collect(range(kk, cfg.n_layers), True),
        },
        "final_norm": jnp.asarray(get("norm.weight") + norm_offset, pdt),
    }
    if not cfg.tie_embeddings:
        lm_head = sd.get("lm_head.weight")
        if lm_head is None:
            raise KeyError("untied config but no lm_head.weight in state_dict")
        params["lm_head"] = jnp.asarray(_to_np(lm_head).T, pdt)
    return params


def to_state_dict(cfg: ModelConfig, params) -> Dict[str, np.ndarray]:
    """Inverse of params_from_state_dict (Llama/Mistral/Mixtral-style).

    Returns HF-named numpy arrays ("model."-prefixed), so trained or
    LoRA-merged weights can go back into the torch/transformers world
    (build a Llama/Mixtral ForCausalLM and `load_state_dict`). MoE
    models export to the Mixtral naming (block_sparse_moe); shared
    experts have no HF counterpart and are refused.
    """
    moe = cfg.moe is not None
    if cfg.mla is not None and moe:
        raise NotImplementedError(
            "MLA + MoE export would mix DeepSeek attention names with "
            "Mixtral MLP names — no HF architecture loads that; "
            "dense-MLP MLA models export fine"
        )
    if moe and cfg.moe.num_shared_experts > 0:
        raise NotImplementedError(
            "shared experts have no HF (Mixtral) state_dict equivalent"
        )
    if moe and cfg.moe_every > 1:
        raise NotImplementedError(
            "interleaved dense/MoE stacks (moe_every > 1) have no HF "
            "(Mixtral) state_dict equivalent"
        )
    if cfg.first_k_dense:
        raise NotImplementedError(
            "first_k_dense export is not wired yet (two-stack tree); "
            "import direction is supported"
        )

    def np_(x):
        return np.asarray(x, np.float32)

    # Export norm offset mirrors the import side's _norm_offset: the
    # Gemma family (detected the same way the import config mapping
    # sets it up: GeGLU + scaled embeddings) stores (1 + w) natively,
    # so our internal s exports unchanged; Llama-convention targets
    # store w directly, so s exports as s + 1.
    gemma_family = cfg.activation == "geglu" and cfg.embed_scale
    noff = 0.0 if gemma_family else 1.0
    sd: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np_(params["embed"]),
        "model.norm.weight": np_(params["final_norm"]) + noff,
    }
    layers = params["layers"]
    for i in range(cfg.n_layers):
        base = f"model.layers.{i}."
        if cfg.mla is not None:
            # Re-fuse the split expansions into HF's single kv_b_proj:
            # (kv_rank, H, nope) ++ (kv_rank, H, v) -> (H*(nope+v), rank).
            m = cfg.mla
            a = base + "self_attn."
            sd[a + "kv_a_proj_with_mqa.weight"] = np_(layers["wkv_a"][i]).T
            sd[a + "kv_a_layernorm.weight"] = (
                np_(layers["kv_a_norm"][i]) + 1.0
            )
            kv_b = np.concatenate(
                [np_(layers["wkv_b_k"][i]), np_(layers["wkv_b_v"][i])],
                axis=-1,
            )  # (kv_rank, H, nope + v)
            sd[a + "kv_b_proj.weight"] = kv_b.reshape(
                m.kv_lora_rank, -1
            ).T
            sd[a + "o_proj.weight"] = np_(layers["wo"][i]).T
            if m.q_lora_rank is None:
                sd[a + "q_proj.weight"] = np_(layers["wq"][i]).T
            else:
                sd[a + "q_a_proj.weight"] = np_(layers["wq_a"][i]).T
                sd[a + "q_a_layernorm.weight"] = (
                    np_(layers["q_a_norm"][i]) + 1.0
                )
                sd[a + "q_b_proj.weight"] = np_(layers["wq_b"][i]).T
        else:
            for ours, (theirs, transpose) in _ATTN_MAP.items():
                w = np_(layers[ours][i])
                sd[base + theirs] = w.T if transpose else w
            if cfg.qk_norm:
                sd[base + "self_attn.q_norm.weight"] = (
                    np_(layers["q_norm"][i]) + noff
                )
                sd[base + "self_attn.k_norm.weight"] = (
                    np_(layers["k_norm"][i]) + noff
                )
        if cfg.attn_bias:
            for ours, theirs in _BIAS_MAP.items():
                sd[base + theirs] = np_(layers[ours][i])
        if cfg.attn_out_bias:
            sd[base + "self_attn.o_proj.bias"] = np_(layers["bo"][i])
        if cfg.attn_sink:
            sd[base + "self_attn.sinks"] = np_(layers["sinks"][i])
        if moe and (cfg.moe.scoring == "softmax_topk"
                    or cfg.moe.expert_bias):
            if not (cfg.moe.scoring == "softmax_topk"
                    and cfg.moe.expert_bias):
                raise NotImplementedError(
                    "softmax_topk scoring and expert_bias only export "
                    "TOGETHER (the GPT-OSS layout); no HF architecture "
                    "matches the partial combination"
                )
            # GPT-OSS fused-expert export: re-interleave gate/up.
            sd[base + "mlp.router.weight"] = np_(layers["w_router"][i]).T
            sd[base + "mlp.router.bias"] = np_(layers["b_router"][i])
            wg = np_(layers["w_gate"][i])  # (E, D, F)
            wu = np_(layers["w_up"][i])
            gu = np.empty((*wg.shape[:-1], 2 * wg.shape[-1]), np.float32)
            gu[..., 0::2], gu[..., 1::2] = wg, wu
            sd[base + "mlp.experts.gate_up_proj"] = gu
            bg = np_(layers["b_gate"][i])
            bu = np_(layers["b_up"][i])
            gub = np.empty((*bg.shape[:-1], 2 * bg.shape[-1]), np.float32)
            gub[..., 0::2], gub[..., 1::2] = bg, bu
            sd[base + "mlp.experts.gate_up_proj_bias"] = gub
            sd[base + "mlp.experts.down_proj"] = np_(layers["w_down"][i])
            sd[base + "mlp.experts.down_proj_bias"] = np_(
                layers["b_down"][i]
            )
        elif moe and cfg.qk_norm:
            # qk_norm + MoE is the Qwen3-MoE shape: export its naming.
            sd[base + "mlp.gate.weight"] = np_(layers["w_router"][i]).T
            for ours, proj in _QWEN3_EXPERT_MAP.items():
                stacked = np_(layers[ours][i])
                for j in range(cfg.moe.num_experts):
                    sd[base + f"mlp.experts.{j}.{proj}.weight"] = (
                        stacked[j].T
                    )
        elif moe:
            sd[base + "block_sparse_moe.gate.weight"] = np_(
                layers["w_router"][i]
            ).T
            for ours, theirs in _EXPERT_MAP.items():
                stacked = np_(layers[ours][i])  # (E, in, out)
                for j in range(cfg.moe.num_experts):
                    sd[
                        base + f"block_sparse_moe.experts.{j}.{theirs}.weight"
                    ] = stacked[j].T
        else:
            for ours, (theirs, transpose) in _DENSE_MLP_MAP.items():
                w = np_(layers[ours][i])
                sd[base + theirs] = w.T if transpose else w
        if cfg.loop is not None:
            for ours, theirs in _OURO_NORMS.items():
                sd[base + theirs + ".weight"] = np_(layers[ours][i]) + noff
            continue
        sd[base + "input_layernorm.weight"] = (
            np_(layers["attn_norm"][i]) + noff
        )
        if cfg.post_norms:
            # Gemma-2 sandwich-norm naming.
            sd[base + "post_attention_layernorm.weight"] = (
                np_(layers["post_attn_norm"][i]) + noff
            )
            sd[base + "pre_feedforward_layernorm.weight"] = (
                np_(layers["mlp_norm"][i]) + noff
            )
            sd[base + "post_feedforward_layernorm.weight"] = (
                np_(layers["post_mlp_norm"][i]) + noff
            )
        else:
            sd[base + "post_attention_layernorm.weight"] = (
                np_(layers["mlp_norm"][i]) + noff
            )
    if cfg.tie_embeddings:
        sd["lm_head.weight"] = sd["model.embed_tokens.weight"]
    else:
        sd["lm_head.weight"] = np_(params["lm_head"]).T
    if cfg.loop is not None:
        gate = params["loop_gate"]
        sd["model.early_exit_gate.weight"] = np_(gate["w"]).reshape(1, -1)
        sd["model.early_exit_gate.bias"] = np_(gate["b"]).reshape(1)
    return sd


def from_hf(model_or_path, dtype=None):
    """(cfg, params) from a transformers model instance or local directory."""
    if isinstance(model_or_path, str):
        from transformers import AutoModelForCausalLM

        model = AutoModelForCausalLM.from_pretrained(
            model_or_path, local_files_only=True
        )
    else:
        model = model_or_path
    cfg = config_from_hf(model.config)
    params = params_from_state_dict(
        model.state_dict(), cfg, dtype=dtype,
        norm_offset=_norm_offset(model.config),
    )
    return cfg, params
