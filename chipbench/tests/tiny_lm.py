"""A tiny copy of the language-model training cell for the CPU tests: every
mechanism stays in the code paths, only sizes shrink. Import AFTER pinning
JAX to the CPU."""
import copy

from tiny import harness


def train_lm_cell():
    """Latent attention with narrower v heads, 8 experts top-2 of which 4
    are held, a dense layer first: d 64, 1 + 2 layers, 64 of 256 vocabulary
    rows held, 2 rows of 32 tokens."""
    cell = copy.deepcopy(harness.load_cell("kanana2-train-ep8share-s4096"))
    cell["config"].update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        qk_nope_head_dim=24, qk_rope_head_dim=8, qk_head_dim=32, head_dim=8,
        v_head_dim=16, kv_lora_rank=32, intermediate_size=96,
        moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
        num_hidden_layers=3, vocab_size=256, n_routed_experts_held=4,
        vocab_size_held=64)
    cell["config"]["optimizer"]["learning_rate"] = 1e-3
    # 128 (token, pick) pairs a step: three of them flipped by bf16 rounding
    # are over the cell's own limit, which is set for 49,152
    cell["config"]["limits"]["load_gap"] = 0.05
    # leaves of a few thousand elements: the stated bf16 recipe moves the
    # norm of a leaf's change by 0.006 here, over the cell's own limit,
    # which is set for leaves of millions
    cell["config"]["limits"]["delta_norm_gap"] = 0.02
    cell["traffic"].update(batch=2, seq=32, pool_batches=4)
    return cell
