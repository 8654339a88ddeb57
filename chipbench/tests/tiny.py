"""Tiny copies of the cells for the CPU tests: every width of the run stays
in the code paths, only sizes shrink. Import AFTER pinning JAX to the CPU."""
import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402


def train_cell():
    cell = copy.deepcopy(harness.load_cell("resnet50-train-trainer"))
    cell["config"].update(image=64, classes=10)
    cell["config"]["optimizer"]["learning_rate"] = 0.002
    cell["traffic"].update(batch=32, pool_batches=4)
    return cell


def serve_cell():
    """The served configuration's file with its sizes shrunk, under a chat
    mix and an engine of the test's own: no served cell is in
    BENCHMARK.json yet (PERF.md, Open questions)."""
    config = harness.load_json("configs", "opt-1.3b.json")
    config.update(hidden_size=64, num_hidden_layers=2,
                  num_attention_heads=4, ffn_dim=256, vocab_size=512,
                  max_position_embeddings=128)
    traffic = {"generator": "open_loop_poisson", "rate_per_s": 20.0,
               "prompt_tokens": {"median": 16, "sigma": 0.8, "lo": 4,
                                 "hi": 32},
               "output_tokens": {"median": 8, "sigma": 0.7, "lo": 2,
                                 "hi": 16}}
    engine = {"max_running": 4, "page_tokens": 8, "kv_pages": 64,
              "queue_depth": 100000, "reserve": "full"}
    return {"name": "tiny-serve-chat", "entry": {"chips": 1},
            "config": config, "traffic": traffic,
            "cell": {"engine": engine, "control_window_s": 2.0},
            "end_to_end": [], "per_layer": []}
