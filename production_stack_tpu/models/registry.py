"""Model registry: presets for known architectures + HF-dir resolution.

The reference selects models by HF id passed to ``vllm serve``
(`deployment-vllm-multi.yaml:101-118`). Here a model is either a local HF
directory (config.json + safetensors, loaded zero-egress) or a named preset
(random-init — used by tests, benchmarks, and the fake fleet).
"""

from __future__ import annotations

import json
import os
from typing import Callable, Dict, Tuple

from . import (exaone_moe, glm4_moe_lite, llama, mellum, nemotron_h, phi4flash,
               qwen3_next)
from .base import Model, ModelConfig
from .glm4_moe_lite import Glm4MoeLiteConfig
from .llama import LlamaConfig
from .exaone_moe import ExaoneMoeConfig
from .mellum import MellumConfig
from .nemotron_h import NemotronHConfig
from .phi4flash import Phi4FlashConfig
from .qwen3_next import Qwen3NextConfig

# HF ``model_type`` -> (reader of its ``config.json`` keys, the config class
# it returns, the model class bound to such a config). What adding a class
# costs: its module, a row here, a preset below (and rows in
# ``engine/config.py::_refusals`` only for a new kind of page).
MODEL_TYPES: Dict[str, Tuple[Callable[[dict, str], ModelConfig], type, type]] = {
    **{mt: (llama.config_from_hf, LlamaConfig, llama.Llama) for mt in (
        "llama", "mistral", "qwen2", "qwen3", "mixtral", "gemma", "gemma2",
        "ouro")},
    "nemotron_h": (
        nemotron_h.config_from_hf, NemotronHConfig, nemotron_h.NemotronH),
    "glm4_moe_lite": (
        glm4_moe_lite.config_from_hf, Glm4MoeLiteConfig,
        glm4_moe_lite.Glm4MoeLite),
    "phi4flash": (phi4flash.config_from_hf, Phi4FlashConfig, phi4flash.Phi4Flash),
    "qwen3_next": (
        qwen3_next.config_from_hf, Qwen3NextConfig, qwen3_next.Qwen3Next),
    "mellum": (mellum.config_from_hf, MellumConfig, mellum.Mellum),
    "exaone_moe": (
        exaone_moe.config_from_hf, ExaoneMoeConfig, exaone_moe.ExaoneMoe),
}
_MODEL_OF = {config: model for _, config, model in MODEL_TYPES.values()}

# Architecture presets. Shapes match the public configs of each family so
# perf numbers are honest; weights are random-init unless an HF dir is given.
PRESETS: Dict[str, ModelConfig] = {
    # Tiny debug model for unit tests / CPU-mesh e2e (heads divisible by 8
    # so every tp degree the test mesh uses divides cleanly).
    "tiny-llama-debug": LlamaConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=8,
        num_kv_heads=8,
        head_dim=16,
        max_position_embeddings=2048,
        name="tiny-llama-debug",
        eos_token_ids=(0,),
        bos_token_id=None,
        dtype="float32",
    ),
    # ~1B-class model: single-chip bench workhorse.
    "llama-1b": LlamaConfig(
        vocab_size=32768,
        hidden_size=2048,
        intermediate_size=5632,
        num_layers=16,
        num_heads=16,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        name="llama-1b",
        eos_token_ids=(2,),
    ),
    # Llama-3-8B shapes (the BASELINE.md flagship target).
    "llama-3-8b": LlamaConfig(
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        max_position_embeddings=131072,
        name="llama-3-8b",
        eos_token_ids=(128001, 128009),
        bos_token_id=128000,
    ),
    # Llama-3-70B shapes (pipeline-parallel multi-host config ladder rung 5).
    "llama-3-70b": LlamaConfig(
        vocab_size=128256,
        hidden_size=8192,
        intermediate_size=28672,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        max_position_embeddings=131072,
        name="llama-3-70b",
        eos_token_ids=(128001, 128009),
        bos_token_id=128000,
    ),
    "mistral-7b": LlamaConfig(
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1000000.0,
        max_position_embeddings=32768,
        name="mistral-7b",
        eos_token_ids=(2,),
    ),
    # Tiny MoE debug model (Mixtral-style sparse MLP; 4 experts, top-2).
    "tiny-mixtral-debug": LlamaConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=8,
        num_kv_heads=8,
        head_dim=16,
        max_position_embeddings=2048,
        num_experts=4,
        num_experts_per_tok=2,
        name="tiny-mixtral-debug",
        eos_token_ids=(0,),
        bos_token_id=None,
        dtype="float32",
    ),
    # Mixtral-8x7B shapes (sparse MoE flagship; 47B params, 13B active).
    "mixtral-8x7b": LlamaConfig(
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1000000.0,
        max_position_embeddings=32768,
        num_experts=8,
        num_experts_per_tok=2,
        name="mixtral-8x7b",
        eos_token_ids=(2,),
    ),
    # Tiny Gemma-1-style debug model (GeGLU, (1+w) norms, scaled embeddings,
    # tied head).
    "tiny-gemma-debug": LlamaConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=8,
        num_kv_heads=4,
        head_dim=16,
        max_position_embeddings=2048,
        hidden_act="gelu_tanh",
        norm_unit_offset=True,
        embed_scale=True,
        tie_word_embeddings=True,
        name="tiny-gemma-debug",
        eos_token_ids=(0,),
        bos_token_id=None,
        dtype="float32",
    ),
    # Tiny Gemma-2-style debug model (adds logit softcaps, post-block norms,
    # alternating sliding-window layers).
    "tiny-gemma2-debug": LlamaConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=4,
        num_heads=8,
        num_kv_heads=4,
        head_dim=16,
        max_position_embeddings=2048,
        hidden_act="gelu_tanh",
        norm_unit_offset=True,
        embed_scale=True,
        tie_word_embeddings=True,
        query_pre_attn_scalar=32.0,
        attn_logit_softcap=50.0,
        final_logit_softcap=30.0,
        post_block_norms=True,
        sliding_window=16,
        sliding_window_pattern=2,
        name="tiny-gemma2-debug",
        eos_token_ids=(0,),
        bos_token_id=None,
        dtype="float32",
    ),
    "gemma-7b": LlamaConfig(
        vocab_size=256000,
        hidden_size=3072,
        intermediate_size=24576,
        num_layers=28,
        num_heads=16,
        num_kv_heads=16,
        head_dim=256,
        rope_theta=10000.0,
        max_position_embeddings=8192,
        hidden_act="gelu_tanh",
        norm_unit_offset=True,
        embed_scale=True,
        tie_word_embeddings=True,
        name="gemma-7b",
        eos_token_ids=(1,),
        bos_token_id=2,
    ),
    "gemma2-9b": LlamaConfig(
        vocab_size=256000,
        hidden_size=3584,
        intermediate_size=14336,
        num_layers=42,
        num_heads=16,
        num_kv_heads=8,
        head_dim=256,
        rope_theta=10000.0,
        max_position_embeddings=8192,
        hidden_act="gelu_tanh",
        norm_unit_offset=True,
        embed_scale=True,
        tie_word_embeddings=True,
        query_pre_attn_scalar=256.0,
        attn_logit_softcap=50.0,
        final_logit_softcap=30.0,
        post_block_norms=True,
        sliding_window=4096,
        sliding_window_pattern=2,
        name="gemma2-9b",
        eos_token_ids=(1,),
        bos_token_id=2,
    ),
    # Tiny Qwen3-style debug model (per-head q/k RMSNorm, no QKV bias).
    "tiny-qwen3-debug": LlamaConfig(
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_layers=2,
        num_heads=8,
        num_kv_heads=4,
        head_dim=16,
        max_position_embeddings=2048,
        qk_norm=True,
        name="tiny-qwen3-debug",
        eos_token_ids=(0,),
        bos_token_id=None,
        dtype="float32",
    ),
    "qwen3-8b": LlamaConfig(
        vocab_size=151936,
        hidden_size=4096,
        intermediate_size=12288,
        num_layers=36,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1000000.0,
        max_position_embeddings=40960,
        qk_norm=True,
        name="qwen3-8b",
        eos_token_ids=(151645, 151643),
        bos_token_id=None,
    ),
    "qwen2-7b": LlamaConfig(
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        head_dim=128,
        rope_theta=1000000.0,
        attention_bias=True,
        name="qwen2-7b",
        eos_token_ids=(151645, 151643),
        bos_token_id=None,
    ),
    # Tiny looped stack: three layers run twice over one set of weights (six
    # cache slots), the four-norm block, one key-value head a query head.
    "tiny-ouro-debug": LlamaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_layers=3,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        rope_theta=1000000.0,
        rms_norm_eps=1e-6,
        max_position_embeddings=2048,
        post_block_norms=True,
        ut_steps=2,
        exit_gate=True,
        name="tiny-ouro-debug",
        eos_token_ids=(0,),
        bos_token_id=None,
        dtype="float32",
    ),
    # Tiny hybrid (state-space + attention + latent MoE) debug model:
    # an expert-parallel share of 4 of 16 experts from expert 4 on.
    "tiny-nemotron-h-debug": NemotronHConfig(
        vocab_size=128,
        hidden_size=64,
        pattern="MEM*E",
        mamba_num_heads=8,
        mamba_head_dim=16,
        n_groups=2,
        ssm_state_size=16,
        conv_kernel=4,
        chunk_size=8,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        n_routed_experts=4,
        router_experts=16,
        expert_first=4,
        num_experts_per_tok=3,
        moe_intermediate_size=32,
        moe_latent_size=16,
        moe_shared_expert_intermediate_size=64,
        routed_scaling_factor=2.5,
        max_position_embeddings=2048,
        name="tiny-nemotron-h-debug",
        eos_token_ids=(0,),
        bos_token_id=None,
        dtype="float32",
    ),
    # Tiny latent-attention mixture of experts: one dense layer, two expert
    # layers of 8 gated experts top 2 and a shared one, a 24 + 8 latent row.
    "tiny-glm4-moe-lite-debug": Glm4MoeLiteConfig(
        vocab_size=128,
        hidden_size=64,
        num_layers=3,
        first_k_dense=1,
        intermediate_size=96,
        num_heads=4,
        q_lora_rank=24,
        kv_lora_rank=24,
        qk_nope_head_dim=12,
        qk_rope_head_dim=8,
        v_head_dim=16,
        rope_theta=10000.0,
        n_routed_experts=8,
        router_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=32,
        routed_scaling_factor=1.8,
        max_position_embeddings=2048,
        name="tiny-glm4-moe-lite-debug",
        eos_token_ids=(0,),
        dtype="float32",
    ),
    # Tiny decoder-hybrid-decoder with the published layer map's shape:
    # Mamba / window at 0-3, Mamba 4 handing on its scan output, full
    # attention 5, a gated memory unit 6, cross-attention 7; window 16.
    "tiny-phi4flash-debug": Phi4FlashConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_layers=8,
        num_heads=4,
        num_kv_heads=2,
        sliding_window=16,
        max_position_embeddings=2048,
        name="tiny-phi4flash-debug",
        eos_token_ids=(0,),
        bos_token_id=None,
        dtype="float32",
    ),
    # Tiny gated-delta-rule hybrid: two periods of three DeltaNet layers and
    # one gated attention layer (rotary on a quarter of the lanes), an
    # expert-parallel share of 4 of 16 experts from expert 4 on, top 3.
    "tiny-qwen3-next-debug": Qwen3NextConfig(
        vocab_size=128,
        hidden_size=64,
        num_layers=8,
        full_attention_interval=4,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        partial_rotary_factor=0.25,
        rope_theta=10000.0,
        linear_num_key_heads=2,
        linear_num_value_heads=4,
        linear_key_head_dim=16,
        linear_value_head_dim=16,
        n_routed_experts=4,
        router_experts=16,
        expert_first=4,
        num_experts_per_tok=3,
        moe_intermediate_size=32,
        shared_expert_intermediate_size=32,
        max_position_embeddings=2048,
        name="tiny-qwen3-next-debug",
        eos_token_ids=(0,),
        bos_token_id=None,
        dtype="float32",
    ),
    # Tiny window / full attention mix: two periods of three window layers
    # (16 tokens) and one full layer under YaRN (factor 4 over 64 original
    # positions), every layer 8 softmax-routed experts top 2, all held.
    "tiny-mellum-debug": MellumConfig(
        vocab_size=128,
        hidden_size=64,
        num_layers=8,
        layer_types=("sliding_attention",) * 3 + ("full_attention",)
        + ("sliding_attention",) * 3 + ("full_attention",),
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        sliding_window=16,
        rope_theta=10000.0,
        full_rope_theta=10000.0,
        yarn_factor=4.0,
        yarn_original_max_position=64,
        yarn_attention_factor=1.1386294361119891,
        n_routed_experts=8,
        router_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=32,
        max_position_embeddings=2048,
        name="tiny-mellum-debug",
        eos_token_ids=(0,),
        bos_token_id=None,
        dtype="float32",
    ),
    # Tiny window / full mix with a draft module: a dense layer, then expert
    # layers (8 sigmoid-routed experts top 2 with a selection bias, one shared
    # expert), `sliding, sliding, sliding, full, sliding` at a 16-token
    # window, and one multi-token-prediction layer (--speculative-mtp 1).
    "tiny-exaone-moe-debug": ExaoneMoeConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_layers=5,
        layer_types=("sliding_attention",) * 3 + ("full_attention",)
        + ("sliding_attention",),
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        sliding_window=16,
        rope_theta=10000.0,
        n_routed_experts=8,
        router_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=32,
        max_position_embeddings=2048,
        name="tiny-exaone-moe-debug",
        eos_token_ids=(0,),
        bos_token_id=None,
        dtype="float32",
    ),
}


def config_from_hf_json(config_path: str, name: str = "") -> ModelConfig:
    """The model config of an HF ``config.json``, read by its
    ``model_type``'s row of :data:`MODEL_TYPES`."""
    with open(config_path) as f:
        hf = json.load(f)
    mt = hf.get("model_type", "llama")
    if mt not in MODEL_TYPES:
        raise ValueError(
            f"unsupported model_type {mt!r} ({'/'.join(MODEL_TYPES)})")
    return MODEL_TYPES[mt][0](hf, name)


def model_for(model_cfg) -> Model:
    """The model class a config belongs to, bound to it: what the runner
    and the benchmark's reference ask for ``init_params``, ``param_pspecs``,
    the cache constructors and ``forward``."""
    return _MODEL_OF[type(model_cfg)](model_cfg)


def get_model_config(model: str) -> ModelConfig:
    """Resolve ``model`` to a config: preset name or local HF directory."""
    if model in PRESETS:
        return PRESETS[model]
    cfg_path = os.path.join(model, "config.json")
    if os.path.isfile(cfg_path):
        return config_from_hf_json(cfg_path, name=model)
    raise ValueError(
        f"unknown model {model!r}: not a preset "
        f"({', '.join(sorted(PRESETS))}) and no local HF dir found"
    )
