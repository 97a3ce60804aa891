"""A configuration small enough to serve and check on a CPU in seconds."""
import copy

TINY = {
    "name": "tiny", "num_hidden_layers": 2, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "vocab_size": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "qk_norm": True,
    "serving": {"max_slots": 4, "max_len": 256, "page_size": 16,
                "num_pages": 96, "prefill_token_budget": 64},
}

CHAT = {
    "kind": "independent",
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.7, "min": 8, "max": 120},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.6, "min": 4, "max": 40},
    "arrivals": {"dist": "exponential"},
    "preload_share": 1.0, "backlog_share": 0.0,
}

SESSIONS = {
    "kind": "sessions",
    "context": {"dist": "lognormal", "median": 60, "sigma": 0.5, "min": 20, "max": 100},
    "append": {"dist": "uniform", "min": 16, "max": 40},
    "extra_requests": {"dist": "geometric", "mean": 2.0},
    "session_gap_s": {"dist": "exponential", "mean": 0.3},
    "output": {"dist": "lognormal", "median": 4, "sigma": 0.6, "min": 1, "max": 12},
    "arrivals": {"dist": "gamma", "cv": 3.0},
    "max_prompt": 180, "preload_share": 0.5, "backlog_share": 0.0,
}

PARAMS = {"rate": 2.0, "check_tokens": 96, "check_requests": 12, "mean_logit_gap": 0.004}

BENCHMARK = {
    "configs": [{"name": "tiny", "file": "-"}],
    "workloads": [{"name": "tiny.chat", "config": "tiny", "traffic": "-", "chips": 1},
                  {"name": "tiny.code", "config": "tiny", "traffic": "-", "chips": 1}],
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "tpot_p95_ms", "unit": "ms"},
                   {"name": "ttft_p95_ms", "unit": "ms", "workloads": ["tiny.code"]}],
    "per_layer": [],
}

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def cell(name="tiny.chat", **params):
    from bench.run import Cell

    mix = SESSIONS if name == "tiny.code" else CHAT
    return Cell(name, BENCHMARK, cfg=copy.deepcopy(TINY), mix=copy.deepcopy(mix),
                params=dict(PARAMS, **params))
