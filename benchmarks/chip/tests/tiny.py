"""A cell small enough for the CPU: the qwen2 path at toy widths."""

import copy
import json

from harness import HERE, Cell

TINY_CONFIG = {
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 128, "vocab_size": 512,
    "rms_norm_eps": 1e-6, "rope_theta": 1e6, "tie_word_embeddings": True,
    "torch_dtype": "float32",
    "program": {"arch": "qwen1.5-0.5b", "family": "qwen2", "mesh": None},
}

# float32 on the CPU: the program and the reference agree to round-off
TINY_LIMITS = {"rows_wrong": 0, "loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-3}


def tiny_cell(mesh: bool = False, **traffic) -> Cell:
    """One device, or with ``mesh`` four on a (data=4, model=1) mesh."""
    t = json.loads((HERE / "traffic" / "short128.json").read_text())
    t.update({"seq_len": 16, "batch": 8, "items_per_chunk": 64, "n_chunks": 8, **traffic})
    config = copy.deepcopy(TINY_CONFIG)
    if mesh:
        config["program"]["mesh"] = {"data": 4, "model": 1}
    return Cell(name="tiny", chips=4 if mesh else 1, config=config, traffic=t,
                limits=dict(TINY_LIMITS),
                end_to_end=[{"name": "tokens_per_s", "unit": "tokens/s"},
                            {"name": "setup_s", "unit": "s"}],
                per_layer=[])
