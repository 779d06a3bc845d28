"""Tiny sizes at which the whole harness runs on the CPU in seconds. Used by
the tests here and by rehearse.py; never by the command line."""

TRAFFIC = {
    "prompt_tokens": {"min": 8, "max": 64, "n": 16, "median": 24},
    "output_tokens": {"min": 4, "max": 16, "n": 16, "median": 8},
    "lead_in_s": 0.5, "drain_limit_s": 30, "rate_rps": 8.0, "check_sample": 3,
}
SERVING = {"n_slots": 4, "block_size": 16, "decode_ticks": 2}

CONFIG = {
    "mistral-7b": {
        "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
        "sliding_window": 4096, "torch_dtype": "float32", "serving": SERVING,
    },
    "deepseek-v2-lite": {
        "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
        "num_experts_per_tok": 2, "n_shared_experts": 1, "moe_intermediate_size": 32,
        "num_hidden_layers": 3, "vocab_size": 256, "torch_dtype": "float32",
        "serving": SERVING,
    },
}
