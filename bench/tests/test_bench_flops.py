"""Operation and byte counts against hand counts at the published shapes."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops as F  # noqa: E402


#: Qwen3-8B's published config.json sizes (no cell runs it yet).
QWEN3_SPEC = {"hidden_size": 4096, "num_hidden_layers": 36,
              "num_attention_heads": 32, "num_key_value_heads": 8,
              "head_dim": 128, "intermediate_size": 12288,
              "vocab_size": 151936, "qk_norm": True}


def dims(name):
    if name == QWEN3:
        return F.Dims.from_spec(QWEN3_SPEC)
    return F.Dims.from_spec(json.loads(
        (ROOT / "bench" / "configs" / f"{name}.json").read_text()))


PHI3 = "phi3-mini-3.8b-k2"
QWEN3 = "qwen3-8b"


@pytest.mark.parametrize("name,layer,weights", [
    # q, k, v, o: 4 x 3072^2; MLP 3 x 3072 x 8192
    (PHI3, 4 * 3072 * 3072 + 3 * 3072 * 8192, 3.8e9),
    # q 4096x4096, k/v 4096x1024 each, o 4096x4096; MLP 3 x 4096 x 12288
    (QWEN3, 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 12288, 8.2e9),
])
def test_layer_params_match_hand_counts(name, layer, weights):
    d = dims(name)
    assert d.layer_params() == layer
    # Layers plus embedding and unembedding land on the published size.
    total = d.n_layers * d.layer_params() + 2 * d.d_model * d.vocab
    assert total == pytest.approx(weights, rel=0.03)


def test_phi3_decode_token_flops_by_hand():
    d = dims(PHI3)
    ctx = 300
    per_layer = 2 * (4 * 3072 * 3072 + 3 * 3072 * 8192) + 4 * 3072 * ctx
    assert F.token_layer_flops(d, ctx) == per_layer
    assert F.head_flops(d) == 2 * 3072 * 32064


def test_qwen3_prefill_flops_by_hand():
    d = dims(QWEN3)
    p = 256
    layer = 2 * d.layer_params() * p + 4 * 4096 * p * (p + 1) // 2
    assert F.prefill_flops(d, p) == 36 * layer + 2 * 4096 * 151936


def test_kv_and_weight_bytes_by_hand():
    q = dims(QWEN3)
    # 36 layers x K and V x 8 kv heads x 128 x 2 bytes per token.
    assert F.kv_bytes(q, 36, 1) == 36 * 2 * 8 * 128 * 2 == 147456
    p = dims(PHI3)
    assert p.layer_weight_bytes() == (p.layer_params() + 2 * 3072) * 2
    assert q.layer_weight_bytes() == (q.layer_params() + 2 * 4096
                                      + 2 * 128) * 2
    assert F.head_weight_bytes(p) == (3072 * 32064 + 3072) * 2


def test_flash_decode_cost_by_hand():
    # Two rows of phi3 (32 heads, 32 kv heads, 96 dims) at 100 and 300
    # positions.
    fl, nb = F.flash_decode_cost([100, 300], 32, 32, 96)
    assert fl == 4 * 32 * 96 * 400
    assert nb == 2 * 400 * 32 * 96 * 2 + 4 * 400 + 2 * 2 * 32 * 96 * 2


def test_entropy_exit_cost_and_bound():
    fl, nb = F.entropy_exit_cost(1, 8, 32064)
    assert fl == 5 * 8 * 32064
    assert nb == 8 * 32064 * 2 + 3 * 4 * 8
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, which = F.least_seconds(fl, nb, peaks)
    assert which == "memory" and t == pytest.approx(nb / 819e9)
    t, which = F.least_seconds(10 ** 15, 1, peaks)
    assert which == "compute" and t == pytest.approx(10 ** 15 / 197e12)
