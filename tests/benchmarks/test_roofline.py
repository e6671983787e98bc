"""The peaks table and the byte and FLOP counts, at shapes small
enough to count by hand."""
import json
import os

import pytest

from benchmarks import manifest, roofline
from benchmarks.roofline import decode_step, flash, model

TINY = {'hidden_size': 8, 'num_hidden_layers': 2, 'num_attention_heads': 4,
        'num_key_value_heads': 2, 'head_dim': 2, 'intermediate_size': 16,
        'vocab_size': 32}


def test_peaks_table_has_the_v5e_and_its_source():
    with open(os.path.join(manifest.HERE, 'peaks.json')) as f:
        table = json.load(f)
    assert 'Google Cloud' in table['source']
    p = roofline.peaks_for('TPU v5 lite')
    assert p['bf16_flops'] == 197e12
    assert p['hbm_bytes_per_s'] == 819e9
    assert p['hbm_bytes'] == 16e9


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(KeyError):
        roofline.peaks_for('TPU v9')


def test_matmul_params_by_hand():
    # per layer: q 8*4*2, o 4*2*8, k and v 8*2*2 each, three 8x16 MLP
    per_layer = 64 + 64 + 32 + 32 + 3 * 128
    assert model.matmul_params(TINY) == 2 * per_layer + 8 * 32
    assert model.weight_bytes(TINY) == (2 * per_layer + 256 + 5 * 8) * 2
    assert model.kv_bytes_per_token(TINY) == 2 * 2 * 2 * 2 * 2


@pytest.mark.parametrize('name,expected', [
    ('internlm2-1.8b', 1889110016), ('mistral-7b-v0.3', 7248023552)])
def test_published_parameter_counts(name, expected):
    with open(os.path.join(manifest.HERE, 'configs', name + '.json')) as f:
        cfg = json.load(f)
    d, L = cfg['hidden_size'], cfg['num_hidden_layers']
    # matmul parameters + the embedding table + the norm vectors
    total = model.matmul_params(cfg) + cfg['vocab_size'] * d + (2 * L + 1) * d
    assert total == expected == cfg['parameters']


def test_forward_flops_and_causal_pairs():
    assert model.causal_pairs(0, 4) == 1 + 2 + 3 + 4
    assert model.causal_pairs(10, 2) == 11 + 12
    n = model.matmul_params(TINY)
    assert model.forward_flops(TINY, 3, 6) == 2 * n * 3 + 4 * 2 * 4 * 2 * 6
    # training is three forward passes' worth, rematerialization not counted
    assert model.train_flops_per_step(TINY, 2, 4) == \
        3 * 2 * model.forward_flops(TINY, 4, 10)


@pytest.mark.parametrize('chips', [1, 4])
def test_decode_step_counts(chips):
    flops, nbytes = decode_step.ops_and_bytes(TINY, active=3,
                                              live_tokens=100, chips=chips)
    assert nbytes == (model.weight_bytes(TINY)
                      + 100 * model.kv_bytes_per_token(TINY)) / chips
    assert flops == (2 * model.matmul_params(TINY) * 3
                     + 4 * 2 * 4 * 2 * 100) / chips


@pytest.mark.parametrize('kernel,products,reads_q,reads_kv', [
    ('flash_fwd', 2, 2, 2), ('flash_dq', 3, 3, 2), ('flash_dkv', 4, 2, 4)])
def test_flash_counts(kernel, products, reads_q, reads_kv):
    b, hq, hkv, s, d = 2, 4, 2, 8, 16
    flops, nbytes = flash.KERNELS[kernel](b, hq, hkv, s, d)
    pairs = b * hq * s * (s + 1) / 2
    assert flops == products * 2 * d * pairs
    assert nbytes == (reads_q * b * hq * s * d + reads_kv * b * hkv * s * d) * 2


def test_roofline_share_takes_the_larger_bound():
    peaks = {'bf16_flops': 100.0, 'hbm_bytes_per_s': 10.0}
    # 50 FLOPs need 0.5 s, 20 bytes need 2 s: memory bounds; took 4 s
    assert roofline.roofline_share(50, 20, 4.0, peaks) == pytest.approx(50.0)
    assert roofline.roofline_share(500, 20, 10.0, peaks) == pytest.approx(50.0)


def test_real_decode_step_is_memory_bound_on_the_v5e():
    with open(os.path.join(manifest.HERE, 'configs',
                           'internlm2-1.8b.json')) as f:
        cfg = json.load(f)
    flops, nbytes = decode_step.ops_and_bytes(cfg, 48, 48 * 400)
    p = roofline.peaks_for('TPU v5 lite')
    assert nbytes / p['hbm_bytes_per_s'] > flops / p['bf16_flops']
    assert 3.5e9 < nbytes < 6e9
