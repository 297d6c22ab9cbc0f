"""The peak table and the model-FLOPs count."""
import json

import pytest

from yardstick import flops, peaks, spec


def test_v5e_peaks_and_source():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice, match="TPU v9"):
        peaks.peaks("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_smollm_360m_flops_by_hand():
    with open(spec.HERE / "configs" / "smollm-360m.json") as f:
        m = json.load(f)["model"]
    # per layer: q and o 960 x 960, k and v 960 x 320, MLP 3 x 960 x 2560
    per_layer = 2 * 960 * 960 + 2 * 960 * 320 + 3 * 960 * 2560
    assert per_layer == 9_830_400
    head = 49152 * 960                        # tied embedding as LM head
    assert flops.matmul_params(m) == 32 * per_layer + head == 361_758_720
    # causal attention: 6 x layers x seq x (heads x head_dim)
    attn = 6 * 32 * 2048 * 960
    assert flops.train_flops_per_token(m, 2048) == 6 * 361_758_720 + attn
    assert flops.train_flops_per_token(m, 2048) == pytest.approx(2.548e9,
                                                                 rel=1e-3)
