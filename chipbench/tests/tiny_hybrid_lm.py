"""A tiny copy of the hybrid (Mamba-2 / experts / attention) language-model
training cell for the CPU tests: every mechanism stays in the code paths,
only sizes shrink. Import AFTER pinning JAX to the CPU."""
import copy

from tiny import harness


def train_hybrid_lm_cell():
    """Published layers 0-8 (MEMEM*EME) at d 64: mixers of 8 heads of 8 on
    2 groups of state 8, chunks of 8; 16 query heads on 1 key/value head of
    8 (groups of 16); 16 experts top-3 of width 32, 4 held, a shared expert
    of 48; 64 of 256 vocabulary rows held; 1 row of 32 tokens (four
    chunks)."""
    cell = copy.deepcopy(harness.load_cell(
        "nemotron3-nano-train-ep16share-s8192"))
    cell["config"].update(
        hidden_size=64, mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
        ssm_state_size=8, chunk_size=8, num_attention_heads=16,
        num_key_value_heads=1, head_dim=8, n_routed_experts=16,
        num_experts_per_tok=3, moe_intermediate_size=32,
        moe_shared_expert_intermediate_size=48, vocab_size=256,
        n_routed_experts_held=4, vocab_size_held=64)
    cell["config"]["optimizer"]["learning_rate"] = 1e-3
    # 96 (token, pick) pairs a step and leaves of a few thousand elements:
    # as tiny_lm.py says of the other language-model cell's limits
    cell["config"]["limits"]["load_gap"] = 0.05
    cell["config"]["limits"]["delta_norm_gap"] = 0.02
    cell["traffic"].update(batch=1, seq=32, pool_batches=4)
    return cell
