"""Numerics tests for the pallas flash-attention kernels (fwd + fused bwd).

Runs the kernels in pallas interpret mode on CPU (same lowering semantics,
no TPU needed) against the jnp reference and its ``jax.vjp`` — the oracle
the fused backward replaces. Block sizes are shrunk so the tests exercise
multi-block online softmax, the causally-skipped dk/dv grid cells, and the
split masked/unmasked loops.

Reference counterpart: the reference has no attention kernels of its own
(delegated to workloads, SURVEY.md §2.11); the oracle here plays the role
its workload-level kernels' unit tests play.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.ops import attention


@pytest.fixture()
def small_blocks(monkeypatch):
    """Shrink kernel blocks so S=384 spans several blocks per kernel."""
    monkeypatch.setattr(attention, 'FWD_BLOCK_Q', 128)
    monkeypatch.setattr(attention, 'FWD_BLOCK_K', 128)
    monkeypatch.setattr(attention, 'DQ_BLOCK_Q', 128)
    monkeypatch.setattr(attention, 'DQ_BLOCK_K', 128)
    monkeypatch.setattr(attention, 'DKV_BLOCK', 128)


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype)


@pytest.mark.parametrize('causal', [True, False])
@pytest.mark.parametrize('group', [1, 2])
def test_flash_fwd_bwd_matches_reference_vjp(small_blocks, causal, group):
    b, hkv, s, d = 2, 2, 384, 64
    hq = hkv * group
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = _rand((b, hq, s, d), ks[0])
    k = _rand((b, hkv, s, d), ks[1])
    v = _rand((b, hkv, s, d), ks[2])
    g = _rand((b, hq, s, d), ks[3])

    o_ref, vjp_ref = jax.vjp(
        lambda a, b_, c: attention.attention_reference(a, b_, c, causal),
        q, k, v)
    o_pal, vjp_pal = jax.vjp(
        lambda a, b_, c: attention._flash_attention(a, b_, c, causal, True),
        q, k, v)

    assert jnp.allclose(o_ref, o_pal, atol=2e-2), 'forward mismatch'
    for name, a, b_ in zip(('dq', 'dk', 'dv'), vjp_ref(g), vjp_pal(g)):
        err = float(jnp.abs(a - b_).max())
        assert err < 5e-2, f'{name} max err {err}'


def test_flash_fwd_lse_is_logsumexp(small_blocks):
    b, h, s, d = 1, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (_rand((b, h, s, d), kk) for kk in ks)
    _, lse = attention._flash_fwd(q, k, v, causal=False, interpret=True)
    scale = d ** -0.5
    logits = jnp.einsum('bhqd,bhkd->bhqk', q, k) * scale
    expect = jax.scipy.special.logsumexp(logits, axis=-1)[..., None]
    assert jnp.allclose(lse, expect, atol=1e-3)


def test_vmem_cap_takes_reference_and_says_so_once(monkeypatch, caplog):
    """Past the resident-K/V cap the whole op (fwd and bwd) takes the
    reference — on a trace-time condition, logged once per shape."""
    monkeypatch.setattr(attention, '_VMEM_CAP_ELEMS', 1)
    monkeypatch.setattr(attention, '_logged_fallbacks', set())
    b, h, s, d = 1, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q, k, v, g = (_rand((b, h, s, d), kk) for kk in ks)
    with caplog.at_level('WARNING', logger=attention.__name__):
        _, vjp = jax.vjp(
            lambda a, b_, c: attention.flash_attention(
                a, b_, c, True, interpret=True), q, k, v)
        attention.flash_attention(q, k, v, True, interpret=True)
    tagged = [r for r in caplog.records
              if attention.FALLBACK_TAG in r.getMessage()]
    assert len(tagged) == 1 and 'VMEM cap' in tagged[0].getMessage()
    _, vjp_ref = jax.vjp(
        lambda a, b_, c: attention.attention_reference(a, b_, c, True),
        q, k, v)
    for a, b_ in zip(vjp(g), vjp_ref(g)):
        assert jnp.allclose(a, b_, atol=1e-3)


def test_flash_gate_falls_back_on_unaligned_seq():
    """Sequence not divisible by 128 uses the reference path (no crash)."""
    b, h, s, d = 1, 2, 100, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (_rand((b, h, s, d), kk) for kk in ks)
    out = attention.flash_attention(q, k, v, causal=True)
    ref = attention.attention_reference(q, k, v, causal=True)
    assert jnp.allclose(out, ref, atol=1e-5)


# -- pallas flash-decode (ops/decode_attention.py) --------------------------


def _decode_reference(q, k_cache, v_cache, lengths, k_s=None, v_s=None):
    """The einsum path from generate._cached_attention, S=1."""
    from skypilot_tpu.models import generate as gen_lib
    out = gen_lib._cached_attention(  # noqa: SLF001 — oracle
        q[:, None], k_cache, v_cache,
        positions=(lengths - 1)[:, None], valid_len=lengths,
        k_s=k_s, v_s=v_s)
    return out[:, 0]


def test_flash_decode_matches_einsum_path():
    from skypilot_tpu.ops import decode_attention

    b, hq, hkv, m, d = 3, 4, 2, 96, 16
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, hq, d), jnp.float32)
    k_cache = jax.random.normal(jax.random.fold_in(key, 1),
                                (b, hkv, m, d), jnp.float32)
    v_cache = jax.random.normal(jax.random.fold_in(key, 2),
                                (b, hkv, m, d), jnp.float32)
    lengths = jnp.asarray([5, 96, 41], jnp.int32)  # mixed, incl. full
    got = decode_attention.flash_decode(q, k_cache, v_cache, lengths,
                                        interpret=True)
    want = _decode_reference(q, k_cache, v_cache, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_quantized_matches_einsum_path():
    from skypilot_tpu.ops import decode_attention

    b, hq, hkv, m, d = 2, 4, 2, 64, 16
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (b, hq, d), jnp.float32)
    kf = jax.random.normal(jax.random.fold_in(key, 1), (b, hkv, m, d))
    vf = jax.random.normal(jax.random.fold_in(key, 2), (b, hkv, m, d))
    # Quantize the way the cache write path does (per-position scales).
    k_s = jnp.maximum(jnp.max(jnp.abs(kf), -1) / 127.0, 1e-8)
    v_s = jnp.maximum(jnp.max(jnp.abs(vf), -1) / 127.0, 1e-8)
    k8 = jnp.clip(jnp.round(kf / k_s[..., None]), -127, 127).astype(
        jnp.int8)
    v8 = jnp.clip(jnp.round(vf / v_s[..., None]), -127, 127).astype(
        jnp.int8)
    lengths = jnp.asarray([33, 64], jnp.int32)
    got = decode_attention.flash_decode(q, k8, v8, lengths, k_s, v_s,
                                        interpret=True)
    want = _decode_reference(q, k8, v8, lengths, k_s, v_s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_multi_block_matches_einsum_path():
    """The inner block loop across several cache blocks (incl. rows
    whose valid length ends mid-block) must agree with the einsum
    path — pl.ds clamping on a partial tail block once silently
    mislabeled key positions, hence divisor-only blocks."""
    from skypilot_tpu.ops import decode_attention

    b, hq, hkv, m, d = 2, 4, 2, 256, 16
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (b, hq, d), jnp.float32)
    k_cache = jax.random.normal(jax.random.fold_in(key, 1),
                                (b, hkv, m, d), jnp.float32)
    v_cache = jax.random.normal(jax.random.fold_in(key, 2),
                                (b, hkv, m, d), jnp.float32)
    lengths = jnp.asarray([97, 256], jnp.int32)  # mid-block + full
    got = decode_attention.flash_decode(q, k_cache, v_cache, lengths,
                                        interpret=True, block_k=64)
    want = _decode_reference(q, k_cache, v_cache, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_geometry_gate():
    from skypilot_tpu.ops import decode_attention

    assert decode_attention.fits(1024, 128)
    assert not decode_attention.fits(1000, 128)       # not 128-divisible
    assert not decode_attention.fits(32768, 128)      # VMEM cap
    assert decode_attention._pick_block(1024) == 512
    assert decode_attention._pick_block(640) == 128   # largest divisor


def test_flash_decode_opt_in_end_to_end(monkeypatch):
    """With the kernel latched on, the decode-step logits through the
    kernel match the einsum path's closely (interpret mode, asked for
    by name).
    The flag is latched at import (module jits cache compiled paths),
    so tests patch the module attribute."""
    from skypilot_tpu.models import generate as gen_lib
    from skypilot_tpu.models import llama

    cfg = llama.TINY
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(5), (2, 7), 0,
                                cfg.vocab_size)
    cache = gen_lib.init_cache(cfg, 2, 128)  # 128-divisible: fits()
    logits, cache = gen_lib.forward_cached(params, prompt, cache, cfg)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    ref_logits, _ = gen_lib.forward_cached(params, tok, cache, cfg)
    monkeypatch.setattr(gen_lib, '_DECODE_KERNEL', 'interpret')
    ker_logits, _ = gen_lib.forward_cached(params, tok, cache, cfg)
    # bf16 activations: per-path accumulation-order noise is ~0.03 in
    # logit units; the check is that the kernel is wired in and sane.
    np.testing.assert_allclose(np.asarray(ker_logits),
                               np.asarray(ref_logits), atol=8e-2)
