"""A cell small enough for the CPU: Qwen3's layer at toy widths."""
import copy

from benchlib import spec

CONFIG = {
    "name": "qwen3-tiny", "num_hidden_layers": 4, "hidden_size": 256,
    "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 32,
    "intermediate_size": 512, "vocab_size": 512, "rms_norm_eps": 1e-06,
    "rope_theta": 1000000, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "program_config": "qwen3-4b",
    "reference": "qwen3.py", "kernel": "packed_attention",
    "engine": {"tp": 1, "max_slots": 4, "max_len": 256,
               "discrete_sizes": [32, 16, 8], "kv_buckets": [64, 128, 256],
               "async_depth": 1, "kv_dtype": "bf16", "temperature": 0.0},
    "correct": {"mean_logit_gap": 0.0008, "min_tokens": 256, "max_requests": 8,
                "batch": 4},
}
CLOSED = {"kind": "closed", "requests": 2048, "block": 16, "shape_seed": 0,
          "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                     "min": 8, "max": 180},
          "output": {"dist": "lognormal", "median": 32, "sigma": 0.5,
                     "min": 8, "max": 64},
          "ramp_finished": 2}
OPEN = {"kind": "open", "rate": 20.0, "block": 16, "shape_seed": 0,
        "prompt": CLOSED["prompt"], "output": CLOSED["output"],
        "ramp_s": 0.5, "drain_s": 30.0}


def cell(kind: str = "closed", tp: int = 1) -> spec.Cell:
    """The tiny cell with the end-to-end metrics of its traffic kind."""
    conf = copy.deepcopy(CONFIG)
    conf["engine"]["tp"] = tp
    names = {"closed": ("tokens_per_s", "setup_s"),
             "open": ("ttft_p90_ms", "tpot_p90_ms", "setup_s")}[kind]
    units = {"tokens_per_s": "tokens/s", "setup_s": "s"}
    metrics = [spec.Metric(n, units.get(n, "ms"), spec.load_module(
        spec.BENCH / "metrics" / f"{n}.py").read) for n in names]
    return spec.Cell(
        name=f"tiny.{kind}", chips=tp, config=conf,
        traffic=copy.deepcopy(CLOSED if kind == "closed" else OPEN),
        end_to_end=metrics, per_layer=[])
