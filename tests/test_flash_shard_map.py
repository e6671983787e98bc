"""Flash attention under a mesh: ``jax.shard_map`` over the batch and
heads axes runs the kernel per shard (GSPMD cannot partition a Mosaic
custom call). On the 8-device virtual CPU mesh, in interpret mode, the
sharded kernel must equal the jnp reference — forward and gradients —
and the train loss must come out the same through it."""
import dataclasses
import functools
import re

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


# -- paged decode per kv-head shard (ops/decode_attention.paged_decode) -----


@pytest.mark.parametrize('s', [1, 3], ids=['s1-kernel', 's3-gather'])
@pytest.mark.parametrize('axes', [dict(tensor=4), dict(data=2, tensor=2)],
                         ids=['tensor4', 'data2-tensor2'])
def test_sharded_paged_step_equals_unsharded(axes, s, monkeypatch):
    """Under a TP mesh the cache write and read of a layer run per
    kv-head shard of the WHOLE pools (heads are independent): the
    kernel, which writes the step's row itself (S = 1), or the row
    scatter and the gathered view (S = 3: the verify, the shared-prefix
    prefill). The layer, tables
    and lengths go to every shard whole, whatever the mesh does with
    the batch. Four virtual devices, interpret mode: the sharded step
    equals the unsharded one, keeps heads and pools sharded, and
    gathers no pool."""
    from skypilot_tpu.models import paged
    from skypilot_tpu.ops import decode_attention

    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    mesh = _mesh(**axes)
    ctx = gen_lib.kernel_shard_ctx(mesh, RULES)
    b, hq, hkv, p, d, nb = 4, 8, 4, 16, 128, 9
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, hq, d))
    kp, vp = (jax.random.normal(jax.random.fold_in(key, i),
                                (3, nb, hkv, p, d)) for i in (1, 2))
    kt, vt = (jax.random.normal(jax.random.fold_in(key, i),
                                (b, hkv, s, d)) for i in (3, 4))
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [1, 2, 7, 0],
                          [8, 0, 0, 0]], jnp.int32)
    lengths = jnp.asarray([61, 16, 39, 2], jnp.int32)
    active = jnp.asarray([True, True, True, False])
    args = (q, kt, vt, kp, vp, tables, lengths, active)

    def step(shard_ctx):  # off the TPU it runs the interpreter
        # skylint: allow-jit(test-only numerics check)
        return jax.jit(lambda q, kt, vt, kp, vp, *a: paged._cache_step(
            q, kt, vt, (kp, vp, None, None), jnp.int32(1), *a, shard_ctx))

    got, want = step(ctx)(*args), step(None)(*args)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=2e-5)
    att, (k_new, _, _, _) = got
    if s == 1:  # the kernel: the inactive row read nothing
        assert not np.asarray(att[3]).any()
    # The write landed in layer 1 alone: row 1's 17th position is block
    # 6, offset 0; the inactive row's went to layer 0's junk sink (the
    # scatter's) or nowhere (the kernel's).
    np.testing.assert_array_equal(k_new[1, 6, :, 0], kt[1, :, 0])
    np.testing.assert_array_equal(k_new[2], kp[2])
    np.testing.assert_array_equal(k_new[0, 1:], kp[0, 1:])
    if s == 1:
        # Per shard the kernel left the pools the row scatter leaves, to
        # the bit, less the junk it no longer writes: nothing in layer 0,
        # and the inactive row's own block 8 as it was.
        np.testing.assert_array_equal(k_new[0], kp[0])
        np.testing.assert_array_equal(k_new[1, 8], kp[1, 8])
        for pool, new, old in ((k_new, kt, kp), (got[1][1], vt, vp)):
            np.testing.assert_array_equal(pool, paged.pool_write(
                old, 1, tables, lengths, new, active).at[0, 0].set(
                    old[0, 0]))
    spec = jax.sharding.PartitionSpec
    assert att.sharding.is_equivalent_to(jax.sharding.NamedSharding(
        mesh, spec(None, None, ctx[1][1], None)), att.ndim)
    assert k_new.sharding.is_equivalent_to(jax.sharding.NamedSharding(
        mesh, spec(None, None, ctx[2][1], None, None)), k_new.ndim)
    place = lambda x, s: jax.device_put(  # noqa: E731
        x, jax.sharding.NamedSharding(mesh, s))
    sharded = (place(q, spec(None, None, ctx[1][1], None)),
               *(place(x, spec(None, ctx[2][1], None, None))
                 for x in (kt, vt)),
               *(place(x, spec(None, None, ctx[2][1], None, None))
                 for x in (kp, vp)), tables, lengths, active)
    hlo = step(ctx).lower(*sharded).compile().as_text()
    assert 'all-gather' not in hlo and 'all-to-all' not in hlo


def test_tp_paged_engine_decodes_through_the_sharded_kernel(monkeypatch):
    """A paged engine under a four-way tensor mesh builds the shard
    context without any flag, its decode step runs the kernel per head
    shard, and the tokens are the unsharded gather engine's (float32:
    the paths differ by accumulation order alone)."""
    from skypilot_tpu.models import engine as engine_lib
    from skypilot_tpu.ops import decode_attention

    cfg = dataclasses.replace(llama.TINY_MH, head_dim=128,
                              dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rows = [[5, 6, 7], [8, 9, 10, 11, 12], [13, 14]]  # > slots: reuse

    def run(**kw):
        engine_lib._jit_paged_chunk.clear_cache()
        eng = engine_lib.ContinuousEngine(
            params, cfg, slots=2, max_len=64, chunk_steps=2, **kw)
        eng.start()
        try:
            futs = [eng.submit(r, 6) for r in rows]
            return ([f.result(timeout=300) for f in futs],
                    eng.stats()['decode_attention'], eng._shard_ctx)
        finally:
            eng.stop()
            engine_lib._jit_paged_chunk.clear_cache()

    want, path, ctx = run()
    assert path == 'gather' and ctx is None
    monkeypatch.setattr(decode_attention, 'PAGED_INTERPRET', True)
    got, path, ctx = run(mesh=_mesh(tensor=4))
    assert path == 'paged_kernel' and ctx is not None
    assert got == want
