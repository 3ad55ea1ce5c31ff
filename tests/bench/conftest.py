"""Tiny stand-ins of a cell for the benchmark's CPU tests."""
import copy
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_CONF = {
    "name": "qwen3-tiny", "reference": "qwen3", "registry_base": "qwen3-14b",
    "hidden_act": "silu", "attention_bias": False, "head_dim": 16,
    "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 40960, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "use_sliding_window": False,
    "tie_word_embeddings": False, "vocab_size": 256,
    "serving": {"max_active": 4, "page_size": 16, "max_len": 192,
                "hbm_pages": 48, "host_pages": 96, "default_period": 4,
                "profile_steps": 8, "trial_steps": 4,
                "warm_joiners": [1, 2, 4], "warm_macro_steps": [1, 2, 4, 8],
                "warm_moves": 48},
}
TINY_MIX = {
    "name": "tiny", "arrivals": {"kind": "jittered", "jitter": 1.0},
    "prompt": {"dist": "lognormal", "median": 48, "sigma": 0.5, "min": 32,
               "max": 128},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4,
               "max": 32},
}


@pytest.fixture
def tiny_cell():
    from bench import harness
    return harness.Cell(
        name="qwen3-tiny.tiny", chips=1, conf=copy.deepcopy(TINY_CONF),
        mix=copy.deepcopy(TINY_MIX),
        cell={"rate_per_s": 8.0, "limits": {"max_logit_gap": 0.05}},
        end_to_end=[{"name": n, "unit": u} for n, u in (
            ("output_tok_s", "tokens/s"), ("ttft_p90_ms", "ms"),
            ("setup_s", "s"))],
        per_layer=[])
