"""FLOPs per token, MFU and roofline shares from shapes."""

import json
import os

import pytest

from ftbench import flops
from ftbench.harness import llama_config, shapes_of

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIGS = ["mistral-7b-v0.3-1x1", "mistral-7b-v0.3-2on1"]


def _config(name):
    with open(os.path.join(ROOT, "ftbench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_parameter_count_agrees_with_the_model(name):
    from torchft_tpu.models.llama import Llama

    config = _config(name)
    assert flops.num_params(shapes_of(config)) == Llama(llama_config(config)).num_params()


def test_mistral_7b_sizes():
    shapes = shapes_of(_config("mistral-7b-v0.3-1x1"))
    one_layer = (flops.matmul_params(dict(shapes, n_layers=1)) - 4096 * 32768)
    assert one_layer == 218_103_808  # 218.1 M a layer, as the issue reckons
    assert flops.num_params(dict(shapes, n_layers=32)) == pytest.approx(7.248e9, rel=1e-3)
    assert flops.num_params(shapes) == pytest.approx(1140.9e6, rel=1e-3)
    assert flops.num_params(shapes_of(_config("mistral-7b-v0.3-2on1"))) == pytest.approx(486.6e6, rel=1e-3)


def test_flops_per_token_is_6n_plus_attention():
    shapes = shapes_of(_config("mistral-7b-v0.3-1x1"))
    n = 4 * 218_103_808 + 4096 * 32768
    assert flops.train_flops_per_token(shapes, 2048) == 6.0 * n + 12.0 * 4 * 4096 * 2048


def test_mfu_from_shapes_and_peak():
    shapes = shapes_of(_config("mistral-7b-v0.3-1x1"))
    per_token = flops.train_flops_per_token(shapes, 2048)
    at_peak = 197e12 / per_token
    assert flops.mfu_pct(at_peak, shapes, 2048, "TPU v5 lite") == pytest.approx(100.0)
    assert flops.mfu_pct(at_peak / 2, shapes, 2048, "TPU v5 lite") == pytest.approx(50.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")


def test_flash_roofline_is_compute_bound_at_2048():
    shapes = shapes_of(_config("mistral-7b-v0.3-1x1"))
    need_flops = flops.flash_step_flops(shapes, 1, 2048)
    need_bytes = flops.flash_step_bytes(shapes, 1, 2048)
    # 6 matmuls of 2*S*S*D per head, causal half, 32 heads, 4 layers
    assert need_flops == 4 * 6 * 2 * 2048 * 2048 * 128 * 32 * 0.5
    least = need_flops / 197e12
    share = flops.roofline_pct(need_flops, need_bytes, 2 * least, "TPU v5 lite")
    assert share["bound"] == "compute"
    assert share["pct"] == pytest.approx(50.0)
