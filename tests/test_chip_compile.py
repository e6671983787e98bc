"""The decode kernel of the serving path, compiled for the chip it runs
on — a TPU v5e that is described, not attached (the TPU's compiler is
installed beside the CPU backend). The interpreter cannot refuse what
Mosaic refuses: a slice off the tiling, too much VMEM or SMEM, an
operand XLA has to re-lay before the call. Nothing runs, so nothing here
is a time or a result; the numerics are ``tests/test_ops_attention.py``'s
and the chip's own (``chip_smoke.py``, ``kernels`` phase).

The topology is described inside a fixture, and only this file does it:
one process at a time may load the TPU's library (see
/opt/skills/guides/on-chip-measurement/SKILL.md §2)."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from skypilot_tpu.models import engine as engine_lib
from skypilot_tpu.models import llama, paged
from skypilot_tpu.ops import attention, decode_attention

# The benchmark's serving cells: 48 slots, max_len 2048 in blocks of 16
# out of a pool of 2,049, 16/8 heads x 128, bf16.
CELL = dict(slots=48, max_blocks=128, block=16, blocks=2049, hq=16,
            hkv=8, d=128)


@pytest.fixture(scope='module')
def one_chip():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 — no compiler here: skip
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernel(one_chip, slots, max_blocks, block, blocks, hq, hkv, d):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    plane = sds((blocks, hkv, block, d), jnp.bfloat16)
    # skylint: allow-jit(test-only compile check)
    return jax.jit(lambda *a: decode_attention.paged_decode(*a)).lower(
        sds((slots, hq, d), jnp.bfloat16), plane, plane,
        sds((slots, max_blocks), jnp.int32),
        sds((slots,), jnp.int32)).compile()


def test_paged_decode_compiles_at_the_cells_geometry(one_chip):
    assert decode_attention.paged_fits(
        CELL['slots'], CELL['max_blocks'], CELL['block'], CELL['d'],
        jnp.bfloat16)
    hlo = _compile_kernel(one_chip, **CELL).as_text()
    # Mosaic's call, under the name the device trace shows.
    assert re.search(r'%paged_decode[.\d]* = .*custom-call\(', hlo)
    assert 'tpu_custom_call' in hlo


def test_paged_fits_is_inside_what_the_chip_takes(one_chip):
    """The largest tables ``paged_fits`` lets through fit the chip's
    SMEM; four times that is what the chip refuses (and the gate with
    it), so the cap is neither idle nor wrong."""
    # 504 x (128 + 1) x 4 B: the last multiple of 8 under the 256 KiB cap.
    assert decode_attention.paged_fits(504, 128, 16, 128, jnp.bfloat16)
    assert not decode_attention.paged_fits(512, 128, 16, 128, jnp.bfloat16)
    _compile_kernel(one_chip, **dict(CELL, slots=504))
    assert not decode_attention.paged_fits(1024, 256, 16, 128,
                                           jnp.bfloat16)
    with pytest.raises(Exception, match='smem'):
        _compile_kernel(one_chip, **dict(CELL, slots=1024, max_blocks=256))


def test_decode_step_hands_the_kernel_the_pool_as_it_lies(one_chip,
                                                          monkeypatch):
    """The whole decode chunk as the engine builds it (two layers of the
    cells' width). XLA lays the pool out for whatever touches it: with
    the [H, D]-slab scatter it re-laid the pool as [NB, P, H, D] inside
    the loop and converted each layer's plane back in front of the
    Mosaic call (PR 26: 9 ms of a 38 ms step on the chip). With
    ``_scatter_rows`` the pool keeps its row-major layout from the
    program's arguments to the call."""
    # The backend is the CPU here; the program under test is the TPU's.
    monkeypatch.setattr(attention, '_use_pallas', lambda: True)
    cfg = llama.LlamaConfig(
        vocab_size=1024, d_model=2048, n_layers=2, n_heads=CELL['hq'],
        n_kv_heads=CELL['hkv'], d_ff=8192, head_dim=CELL['d'],
        max_seq_len=32768)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg)))
    slots = CELL['slots']
    pool = on_chip(jax.eval_shape(lambda: paged.init_pool(
        cfg, slots, CELL['max_blocks'] * CELL['block'], CELL['blocks'],
        CELL['block'])))
    assert paged.decode_path(pool.tables.shape, pool.k.shape, pool.k.dtype,
                             False) == 'paged_kernel'

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (slots,), dtype,
                                    sharding=one_chip)

    hlo = engine_lib._jit_paged_chunk.lower(
        cfg, 2, params, pool, vec(jnp.int32), vec(jnp.float32), None, None,
        vec(jnp.bool_), vec(jnp.uint32, 2), None).compile().as_text()
    call = re.search(r'%paged_decode[.\d]* = [^\n]*custom-call\(([^)]*)\)',
                     hlo)
    assert call, 'the decode step does not call the kernel'
    planes = [name.strip() for name in call.group(1).split(',')][-2:]
    defs = {m.group(1): m.group(2) for m in re.finditer(
        r'(%[\w.-]+) = \S+ ([\w-]+)\(', hlo)}
    # Each plane reaches the call as a view or at most out of XLA's
    # alternate memory (copy-done): never through a layout-changing copy.
    assert all(defs[p] in ('bitcast', 'copy-done', 'get-tuple-element',
                           'fusion') for p in planes), \
        {p: defs[p] for p in planes}
    pool_layouts = set(re.findall(
        r'bf16\[2,2049,8,16,128\]\{([\d,]+)', hlo))
    assert pool_layouts == {'4,3,2,1,0'}, pool_layouts
