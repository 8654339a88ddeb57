"""A tiny copy of the prerouted language-model training cell for the CPU
tests: every mechanism stays in the code paths, only sizes shrink. Import
AFTER pinning JAX to the CPU."""
import copy

from tiny import harness


def train_prerouted_lm_cell():
    """14 query heads on 2 key/value heads of 16 (groups of 7), a global
    layer then two window layers of 16 keys, 8 experts top-3 of which 4
    are held: d 64, 64 of 256 vocabulary rows held, 1 row of 48 tokens
    (three windows)."""
    cell = copy.deepcopy(harness.load_cell(
        "smallthinker-train-ep8share-s16384"))
    cell["config"].update(
        hidden_size=64, num_attention_heads=14, num_key_value_heads=2,
        head_dim=16, moe_ffn_hidden_size=32, moe_num_primary_experts=8,
        n_routed_experts=8, moe_num_active_primary_experts=3,
        num_experts_per_tok=3, num_hidden_layers=3, sliding_window_size=16,
        vocab_size=256, n_routed_experts_held=4, vocab_size_held=64,
        # at 48 positions 1.5e6 barely turns a pair: a rotary fault would
        # hide
        rope_theta=10000,
        # published layers 0 (global), 1 and 2 (window)
        layers_kept=[0, 1, 2])
    cell["config"]["optimizer"]["learning_rate"] = 1e-3
    # 144 (token, pick) pairs a step and leaves of a few thousand elements:
    # as tiny_lm.py says of the other language-model cell's limits
    cell["config"]["limits"]["load_gap"] = 0.05
    cell["config"]["limits"]["delta_norm_gap"] = 0.02
    cell["traffic"].update(batch=1, seq=48, pool_batches=4)
    return cell
