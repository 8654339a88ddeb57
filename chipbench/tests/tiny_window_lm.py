"""A tiny copy of the window / full language-model training cell for the CPU
tests: every mechanism stays in the code paths, only sizes shrink. Import
AFTER pinning JAX to the CPU."""
import copy

from tiny import harness


def train_window_lm_cell():
    """4 query heads on 2 key/value heads of 16, windows of 16 keys on two
    of three layers (a dense layer first), 8 experts top-2 of which 4 are
    held: d 64, 64 of 256 vocabulary rows held, 1 row of 48 tokens (three
    windows)."""
    cell = copy.deepcopy(harness.load_cell(
        "trinity-mini-train-ep8share-s8192"))
    cell["config"].update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        num_experts=8, n_routed_experts=8, num_experts_per_tok=2,
        num_hidden_layers=3, sliding_window=16, vocab_size=256,
        n_routed_experts_held=4, vocab_size_held=64,
        # published layers 0 (sliding), 2 (sliding) and 3 (full)
        layers_kept=[0, 2, 3])
    cell["config"]["optimizer"]["learning_rate"] = 1e-3
    # 96 (token, pick) pairs a step and leaves of a few thousand elements:
    # as tiny_lm.py says of the other language-model cell's limits
    cell["config"]["limits"]["load_gap"] = 0.05
    cell["config"]["limits"]["delta_norm_gap"] = 0.02
    cell["traffic"].update(batch=1, seq=48, pool_batches=4)
    return cell
