"""Flash attention under a mesh: ``jax.shard_map`` over the batch and
heads axes runs the kernel per shard (GSPMD cannot partition a Mosaic
custom call). On the 8-device virtual CPU mesh, in interpret mode, the
sharded kernel must equal the jnp reference — forward and gradients —
and the train loss must come out the same through it."""
import dataclasses
import functools
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import generate as gen_lib
from skypilot_tpu.models import llama
from skypilot_tpu.ops import attention
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.parallel import sharding as sharding_lib

RULES = sharding_lib.ShardingRules()


def _mesh(**axes):
    """A four-device mesh out of the suite's eight virtual devices."""
    return mesh_lib.build_mesh(mesh_lib.MeshSpec(**{'fsdp': 1, **axes}),
                               devices=jax.devices()[:4])


@pytest.mark.parametrize('axes', [dict(data=2, tensor=2), dict(fsdp=4)],
                         ids=['data2-tensor2', 'fsdp4'])
def test_sharded_flash_equals_reference(axes):
    mesh = _mesh(**axes)
    shard = attention.shard_ctx(mesh, RULES)
    assert shard is not None
    b, hq, hkv, s, d = 4, 4, 2, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, g = (jax.random.normal(k, (b, hq, s, d)) for k in (ks[0], ks[3]))
    k, v = (jax.random.normal(kk, (b, hkv, s, d)) for kk in ks[1:3])

    def both(fn):
        # skylint: allow-jit(test-only numerics check)
        return jax.jit(lambda q_, k_, v_: (
            fn(q_, k_, v_),
            jax.grad(lambda *a: jnp.sum(fn(*a) * g),
                     argnums=(0, 1, 2))(q_, k_, v_)))(q, k, v)

    out, grads = both(lambda q_, k_, v_: attention.flash_attention(
        q_, k_, v_, True, interpret=True, shard=shard))
    ref_out, ref_grads = both(
        lambda q_, k_, v_: attention.attention_reference(q_, k_, v_, True))
    np.testing.assert_allclose(out, ref_out, atol=2e-2)
    for got, want in zip(grads, ref_grads):
        np.testing.assert_allclose(got, want, atol=5e-2)
    # Per shard, not gathered: the output keeps the q sharding.
    assert out.sharding.is_equivalent_to(
        jax.sharding.NamedSharding(mesh, shard[1]), out.ndim)


def test_one_device_needs_no_shard_ctx():
    assert attention.shard_ctx(None, RULES) is None
    assert attention.shard_ctx(mesh_lib.single_device_mesh(), RULES) is None


def test_train_loss_through_the_sharded_kernel(monkeypatch):
    """models/llama.py hands the layer stack a shard ctx built from the
    mesh and the rules; the loss through the sharded kernel (interpret
    mode here) equals the loss through the reference with no mesh."""
    cfg = dataclasses.replace(llama.TINY, d_model=128, n_heads=4,
                              n_kv_heads=2, head_dim=64)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 128), 0,
                                cfg.vocab_size)
    want, _ = llama.loss_fn(params, tokens, cfg)  # CPU: the reference
    seen = []
    real = attention.flash_attention

    def interpreted(q, k, v, causal=True, shard=None):
        seen.append(shard)
        return real(q, k, v, causal, interpret=True, shard=shard)

    monkeypatch.setattr(attention, 'flash_attention', interpreted)
    mesh = _mesh(data=2, tensor=2)
    # skylint: allow-jit(test-only numerics check)
    got, _ = jax.jit(functools.partial(
        llama.loss_fn, cfg=cfg, mesh=mesh, rules=RULES))(params, tokens)
    assert seen and all(s is not None and s[0] is mesh for s in seen)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-2)


def test_decode_geometry_fallback_is_said_once(monkeypatch, caplog):
    """A cache the decode kernel cannot take (M not a multiple of 128)
    takes the einsum path on a trace-time condition, logged once per
    shape — never silently."""
    monkeypatch.setattr(gen_lib, '_DECODE_KERNEL', 'interpret')
    monkeypatch.setattr(attention, '_logged_fallbacks', set())
    b, hq, hkv, m, d = 2, 4, 2, 96, 16
    q = jnp.ones((b, 1, hq, d))
    cache = jnp.ones((b, hkv, m, d))
    lengths = jnp.asarray([5, 96], jnp.int32)
    with caplog.at_level('WARNING', logger=attention.__name__):
        for _ in range(2):
            gen_lib._cached_attention(q, cache, cache,
                                      (lengths - 1)[:, None], lengths)
    tagged = [r.getMessage() for r in caplog.records
              if attention.FALLBACK_TAG in r.getMessage()]
    assert len(tagged) == 1 and 'flash_decode' in tagged[0]


def test_decode_kernel_value_is_validated():
    r = subprocess.run(
        [sys.executable, '-c', 'import skypilot_tpu.models.generate'],
        env={**os.environ, 'SKYTPU_DECODE_KERNEL': 'on'},
        capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and "'pallas' or 'interpret'" in r.stderr


def test_the_three_kernels_carry_their_names_into_the_program():
    """``name=`` on each ``pallas_call`` opens a scope of that name, and
    on the chip the compiler names the custom call after it
    (``%flash_fwd.1 = ... custom-call(``): what the device trace and the
    benchmark's breakdown then show."""
    q = jnp.zeros((1, 2, 128, 64), jnp.bfloat16)
    kv = jnp.zeros((1, 1, 128, 64), jnp.bfloat16)

    def loss(q_, k_, v_):
        return attention.flash_attention(
            q_, k_, v_, True, interpret=True).astype(jnp.float32).sum()

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv))
    for name in ('flash_fwd', 'flash_dq', 'flash_dkv'):
        assert re.search(rf'\bname={name}\b', jaxpr), name
