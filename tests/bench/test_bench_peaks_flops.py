"""The peaks table and the model-FLOP count, against hand counts at the
benchmark's configuration and at a one-chip share of qwen2-7b."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.flops import matmul_params, train_flops_per_token  # noqa: E402
from bench.peaks import peaks  # noqa: E402


# qwen2-7b's published widths (hf Qwen/Qwen2-7B) on one pipeline stage of
# two layers, with an eighth of the vocabulary.
QWEN2_SHARE = {"num_layers": 2, "d_model": 3584, "num_heads": 28,
               "num_kv_heads": 4, "head_dim": 128, "d_ff": 18944,
               "vocab_size": 19008, "glu": True}


def _model(name):
    if name == "qwen2-7b-share":
        return QWEN2_SHARE
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())["model"]


def test_v5e_peaks_and_unknown_kind():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


# Hand counts.  qwen1.5-0.5b: per layer 4 * 1024^2 (q, k, v, o; 16 KV
# heads of 64) + 3 * 1024 * 2816 (gated FFN) = 12,845,056; 24 layers plus
# the 1024 x 151936 head = 463,863,808.  Attention at S = 2048:
# 6 * 24 * 2048 * 1024 = 301,989,888 per token.
# qwen2-7b-share: per layer 2 * 3584^2 (q, o) + 2 * 3584 * 512 (k, v: 4 KV
# heads of 128) + 3 * 3584 * 18944 = 233,046,016; 2 layers plus the
# 3584 x 19008 head = 534,216,704.  Attention: 6 * 2 * 2048 * 3584.
@pytest.mark.parametrize("name, params, per_token", [
    ("qwen1.5-0.5b", 463_863_808, 6 * 463_863_808 + 301_989_888),
    ("qwen2-7b-share", 534_216_704, 6 * 534_216_704 + 88_080_384),
])
def test_flops_hand_count(name, params, per_token):
    model = _model(name)
    assert matmul_params(model) == params
    assert train_flops_per_token(model, 2048) == per_token
