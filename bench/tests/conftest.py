import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def tiny_config() -> dict:
    """The 0.5B configuration file shrunk to a size the CPU runs in
    seconds (every width cut: for tests only, never a benchmark cell)."""
    cfg = json.loads((ROOT / "bench" / "configs" / "qwen25-0.5b-awq.json")
                     .read_text())
    cfg.update(name="tiny", num_hidden_layers=2, hidden_size=128,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               intermediate_size=256, vocab_size=512)
    cfg["serving"] = dict(cfg["serving"], num_slots=4, prefill_chunk=16,
                          max_seq=512)
    return cfg


@pytest.fixture
def tiny():
    return tiny_config()
