"""The operation and parameter counts at xLSTM-125m's widths."""

import json
import os

import pytest

from bench import arch, cell, flops

CONFIGS = ("evalmap-xlstm125m",)


def _config(name="evalmap-xlstm125m"):
    return cell.Bench(cell.ROOT).config(name)


def _smoke():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "evalmap-smoke.json")
    with open(path) as f:
        return json.load(f)


def test_parameter_count():
    # 21 mLSTM blocks with dense q/k/v and 3 sLSTM blocks
    assert flops.param_count(_config()) == 277_987_944
    # one 8-block period of them: 7 mLSTM blocks and 1 sLSTM block
    c = dict(_config(), num_blocks=8, slstm_at=[7])
    assert flops.param_count(c) == 118_419_320
    # the program's own 12-block preset, sLSTM at blocks 5 and 11
    c = dict(_config(), num_blocks=12, slstm_at=[5, 11])
    assert flops.param_count(c) == 155_648_720


@pytest.mark.parametrize("name", CONFIGS)
def test_parameter_count_matches_the_program(name):
    from repro.configs import get_arch
    from repro.models import Model
    for c in (_config(name), _smoke()):
        assert flops.param_count(c) == Model(
            get_arch(arch.register(c))).param_count()
    c = dict(_config(name), num_blocks=12, slstm_at=[5, 11])
    assert flops.param_count(c) == Model(get_arch("xlstm-125m")).param_count()


def test_forward_flops_by_hand():
    # d 768, 4 heads, inner 1536 (head 384), sLSTM heads 192, GeGLU 1024,
    # vocab 50304; 21 mLSTM and 3 sLSTM blocks; batch 1 x seq 128
    b, s = 1, 128
    mlstm = 2 * (768 * 3072 + 3 * 1536 * 1536 + 2 * 1536 * 4 + 1536 * 768)
    slstm = 2 * (4 * 768 * 768 + 4 * 4 * 192 * 192 + 768 * 2048
                 + 1024 * 768)
    head = 2 * 768 * 50304
    mix = 4 * 4 * 384 * (128 * 129 // 2)
    want = b * s * (21 * mlstm + 3 * slstm + head) + b * 21 * mix
    assert flops.forward_flops(_config(), b, s) == want
    assert abs(want / 1e9 - 72.1744) < 1e-3


def test_peaks_are_keyed_by_device_kind():
    p = flops.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes"] == 16e9
    try:
        flops.peak("a device nobody measured")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device must be an error")
