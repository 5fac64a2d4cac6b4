"""Operations and bytes from shapes, against counts made by hand for one layer
of the published widths (hidden 4096, 32 heads of 128, 8 KV heads, FFN 14336)."""
from chipbench import common
from chipbench.models import dense_decoder as arch

CFG = common.load_json("configs", "mistral7b-train-1chip.json")


def test_parameters_of_one_layer_and_of_the_model():
    attn = 4096 * 4096 * 2 + 4096 * 1024 * 2            # wq, wo; wk, wv
    mlp = 3 * 4096 * 14336
    assert arch.layer_params(CFG) == attn + mlp + 2 * 4096 == 218_112_000
    assert arch.param_count(dict(CFG, num_hidden_layers=32)) == 32 * 218_112_000 + 2 * 32000 * 4096 + 4096


def test_attended_keys_with_and_without_the_window():
    assert arch.attended_keys(4, None) == (1 + 2 + 3 + 4) / 4
    assert arch.attended_keys(6, 2) == (1 + 2 + 2 + 2 + 2 + 2) / 6
    assert arch.attended_keys(8192, 4096) == (4096 * 4097 / 2 + 4096 * 4096) / 8192


def test_train_flops_per_token_one_layer():
    one = dict(CFG, num_hidden_layers=1)
    matmul = 6 * (218_112_000 - 2 * 4096 + 32000 * 4096)
    attention = 3 * 4 * 32 * 128 * arch.attended_keys(8192, 4096)
    assert arch.train_flops_per_token(one, 8192) == matmul + attention


def test_flash_call_work_and_bound():
    k = common.load_module("kernels", "flash_sdpa")
    peaks = common.peaks("TPU v5 lite")
    w = k.call_work(CFG, 8192, 1)
    pairs = 8192 * arch.attended_keys(8192, 4096)
    assert w["fwd_flops"] == 2 * 2 * 32 * 128 * pairs and w["bwd_flops"] == 2 * w["fwd_flops"]
    assert w["fwd_bytes"] == (2 * 32 + 2 * 8) * 8192 * 128 * 2
    assert k.bound(CFG, 8192, 1, peaks) == "compute"
    least = k.least_seconds(CFG, 8192, 1, peaks, fwd_calls=1, bwd_calls=1)
    assert abs(least - 3 * w["fwd_flops"] / 197e12) < 1e-12


def test_paged_decode_streams_k_and_v_once_a_token_a_layer():
    k = common.load_module("kernels", "paged_attn_decode")
    cfg = common.load_json("configs", "mistral7b-serve-1chip.json")
    w = k.work(cfg, 1000)
    assert w["bytes"] == 16 * 1000 * 2 * 8 * 128 * 2 == 1000 * 65536
    assert k.least_seconds(cfg, 1000, common.peaks("TPU v5 lite")) == w["bytes"] / 819e9


def test_unknown_device_is_an_error():
    import pytest

    with pytest.raises(ValueError):
        common.peaks("TPU v9 imaginary")
