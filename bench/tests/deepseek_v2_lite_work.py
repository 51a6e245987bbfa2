"""Test data only: the program sizes of the DeepSeek-V2 architecture
(latent attention, routed and shared experts, leading dense layers),
as a configuration's work module states them.  A work module that
serves a cell also brings the work counts the readers divide by
(``decode_step``, ``prefill``, ``kernel_bound_s``)."""


def program_sizes(c: dict) -> dict:
    return {"d_model": c["hidden_size"],
            "num_layers": c["num_hidden_layers"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
            "d_ff": c["intermediate_size"],
            "kv_lora_rank": c["kv_lora_rank"],
            "q_lora_rank": c["q_lora_rank"],
            "qk_nope_dim": c["qk_nope_head_dim"],
            "qk_rope_dim": c["qk_rope_head_dim"],
            "v_head_dim": c["v_head_dim"],
            "moe.num_experts": c["n_routed_experts"],
            "moe.top_k": c["num_experts_per_tok"],
            "moe.d_ff_expert": c["moe_intermediate_size"],
            "moe.num_shared": c["n_shared_experts"],
            "moe.d_ff_shared": c["moe_intermediate_size"],
            "moe.norm_topk_prob": c["norm_topk_prob"],
            "vocab_size": c["vocab_size"], "rope_theta": c["rope_theta"],
            "norm_eps": c["rms_norm_eps"],
            "tie_embeddings": c["tie_word_embeddings"],
            "dtype": c["torch_dtype"], "act": c["hidden_act"]}
