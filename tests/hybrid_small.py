"""What the tests of ``ops/ssm.py`` and ``models/hybrid_lm.py`` share: the
benchmark's plain reference for the hybrid decoder, loaded from its file, and
its published configuration at widths in the tens."""

import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)
import hybrid_drivers  # noqa: E402


def _load_reference():
    path = os.path.join(BENCH, "configs", "granite-4.0-h-micro_reference.py")
    spec = importlib.util.spec_from_file_location("granite_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()
with open(os.path.join(BENCH, "configs", "granite-4.0-h-micro.json")) as f:
    PUBLISHED = json.load(f)
#: the published configuration at widths in the tens: a Mamba-2 layer and an
#: attention layer, four query heads over two key-value heads
SMALL = {**PUBLISHED, "hidden_size": 32, "shared_intermediate_size": 48,
         "vocab_size": 40, "layer_types": ["mamba", "attention"],
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 8,
         "mamba_chunk_size": 4, "attention": None, "dtype": "float32",
         "remat_blocks": False}
BATCH, SEQ = 2, 12
