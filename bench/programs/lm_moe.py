"""The program's DeepSeek-V3 path (MLA, a dense prefix, held experts):
``launch/steps.build_lm_train``."""
from __future__ import annotations

import dataclasses


def model_config(sizes: dict):
    """The program's ``TransformerConfig`` for a configuration that keeps
    the published config.json's keys and cuts ``n_layers``,
    ``n_experts_held`` and ``vocab``."""
    import jax.numpy as jnp

    from repro.models.moe import MoEConfig
    from repro.models.transformer import TransformerConfig

    unsupported = {"q_lora_rank": None, "n_group": 1, "topk_group": 1,
                   "hidden_act": "silu", "moe_layer_freq": 1,
                   "attention_bias": False, "tie_word_embeddings": False}
    for key, want in unsupported.items():
        if sizes[key] != want:
            raise ValueError(f"the program's DeepSeek-V3 block needs "
                             f"{key} = {want!r}, got {sizes[key]!r}")
    moe = MoEConfig(
        n_experts=sizes["n_routed_experts"], top_k=sizes["num_experts_per_tok"],
        d_ff_expert=sizes["moe_intermediate_size"],
        shared_d_ff=sizes["n_shared_experts"] * sizes["moe_intermediate_size"],
        score_func=sizes["scoring_func"], norm_topk=sizes["norm_topk_prob"],
        routed_scale=sizes["routed_scaling_factor"],
        seq_aux_coef=sizes["seq_aux_alpha"] if sizes["seq_aux"] else 0.0,
        experts_held=(0, sizes["n_experts_held"]))
    return TransformerConfig(
        name=sizes["name"], n_layers=sizes["n_layers"],
        d_model=sizes["hidden_size"], n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
        d_ff=sizes["intermediate_size"], vocab=sizes["vocab"],
        rope_theta=float(sizes["rope_theta"]), eps=sizes["rms_norm_eps"],
        moe=moe, kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"], latent_eps=sizes["latent_rms_eps"],
        first_k_dense=sizes["first_k_dense_replace"],
        dtype=jnp.dtype(sizes["dtype"]),
        param_dtype=jnp.dtype(sizes["param_dtype"]),
        remat=sizes["remat"], attn_chunk=sizes["attn_chunk"])


def plan(sizes: dict, mix: dict, mesh):
    """The program's training step for ``sizes`` under ``mix`` on ``mesh``."""
    from repro.configs.registry import ShapeCell, get_arch
    from repro.launch.steps import build_lm_train, make_exchange
    from repro.optim.optimizers import OptimizerSpec

    arch = dataclasses.replace(get_arch(sizes["arch"]),
                               config=model_config(sizes),
                               microbatches={"bench": mix["microbatches"]})
    cell = ShapeCell("bench", "train", {"global_batch": mix["global_batch"],
                                        "seq_len": mix["seq_len"]})
    ex = make_exchange(mesh, "lm", mix["strategy"],
                       opt=OptimizerSpec(**sizes["optimizer"]))
    return build_lm_train(arch, cell, mesh, ex)
