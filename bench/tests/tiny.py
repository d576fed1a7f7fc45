"""A tiny configuration and mixes for running the harness on the CPU."""
from __future__ import annotations

import copy
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
              "vocab_size": 256, "rope_theta": 10000.0, "norm_eps": 1e-5,
              "tie_embeddings": False, "mlp_gated": True, "mlp_act": "silu"}


def conf(slots: int = 8, max_len: int = 256) -> dict:
    """yi-6b's configuration file with tiny widths."""
    c = json.loads((ROOT / "bench/configs/yi-6b.json").read_text())
    c["model"] = dict(TINY_MODEL)
    c["serving"].update(max_decode_slots=slots, max_len=max_len)
    return c


def spec(name: str, **over) -> dict:
    """A mix of bench/traffic with its lengths cut to the tiny sizes."""
    s = json.loads((ROOT / f"bench/traffic/{name}.json").read_text())
    s = copy.deepcopy(s)
    s["prompt"] = {"median": 12, "sigma": 0.3, "min": 4, "max": 24}
    s["output"] = {"median": 6, "sigma": 0.3, "min": 3, "max": 10}
    if "shared" in s:
        s["shared"] = dict(s["shared"], tokens=40)
    if "clients" in s:
        s["clients"] = 4
    s.update(over)
    return s


LIMITS = {"max_logit_gap": {"limit": 0.05}, "sample_tokens": 30,
          "sample_requests": 4}

# The benchmark's cells, and the shared-prefix mix whose cell waits for a
# steadier TTFT (PERF.md, open questions).
CELLS = ["yi6b.decode_batch", "yi6b.chat", "internlm2.shared_prefix",
         "internlm2.long_decode"]
