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


def _compile_kernel(one_chip, slots, max_blocks, block, blocks, hq, hkv, d,
                    dtype=jnp.bfloat16):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((2, blocks, hkv, block, d), dtype)
    new = sds((slots, hkv, d), dtype)
    # skylint: allow-jit(test-only compile check)
    return jax.jit(lambda *a: decode_attention.paged_decode(*a),
                   donate_argnums=(3, 4)).lower(
        sds((slots, hq, d), dtype), new, new, pool, pool,
        sds((), jnp.int32), sds((slots, max_blocks), jnp.int32),
        sds((slots,), jnp.int32)).compile()


def _kernel_call(hlo):
    """Operand names of the ``paged_decode`` custom call, whose results
    1 and 2 (the pools) must BE its operands 6 and 7."""
    call = re.search(r'%paged_decode[.\d]* = [^\n]*custom-call\(([^)]*)\)'
                     r'([^\n]*)', hlo)
    assert call, 'no paged_decode call'
    assert re.search(r'output_to_operand_aliasing=\{\{1\}: \(6, \{\}\), '
                     r'\{2\}: \(7, \{\}\)\}', call.group(2)), call.group(2)
    names = [n.split('*/')[-1].strip() for n in call.group(1).split(',')]
    assert len(names) == 8, names
    return names


def test_paged_decode_compiles_at_the_cells_geometry(one_chip):
    assert decode_attention.paged_fits(
        CELL['slots'], CELL['max_blocks'], CELL['block'], CELL['d'],
        jnp.bfloat16)
    compiled = _compile_kernel(one_chip, **CELL)
    hlo = compiled.as_text()
    # Mosaic's call, under the name the device trace shows.
    assert re.search(r'%paged_decode[.\d]* = .*custom-call\(', hlo)
    assert 'tpu_custom_call' in hlo
    # It writes the pools it is handed: results 1 and 2 ARE operands 6
    # and 7 (the donated arguments themselves), and nothing pool-sized
    # is allocated beside them.
    _kernel_call(hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize('dtype,block', [
    (jnp.float32, 8), (jnp.float32, 16), (jnp.bfloat16, 32)],
    ids=['f32-p8', 'f32-p16', 'bf16-p32'])
def test_paged_decode_writes_a_block_of_any_tile_count(one_chip, dtype,
                                                       block):
    """The new row goes into a block-sized slice of the VMEM buffer at
    an offset only the program knows, and that slice is what is DMA'd
    back: one sublane tile (float32 x 8, bfloat16 x 16) or two, the
    other geometries ``paged_fits`` lets through."""
    geometry = dict(CELL, block=block, max_blocks=2048 // block,
                    blocks=32768 // block + 1)
    assert decode_attention.paged_fits(
        CELL['slots'], geometry['max_blocks'], block, CELL['d'], dtype)
    _kernel_call(_compile_kernel(one_chip, **geometry,
                                 dtype=dtype).as_text())


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


def _llama_cell(one_chip):
    """Two layers of the cells' width, parameters and pool described on
    the chip: (cfg, params, pool)."""
    cfg = llama.LlamaConfig(
        vocab_size=1024, d_model=2048, n_layers=2, n_heads=CELL['hq'],
        n_kv_heads=CELL['hkv'], d_ff=8192, head_dim=CELL['d'],
        max_seq_len=32768)

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg)))
    pool = on_chip(jax.eval_shape(lambda: paged.init_pool(
        cfg, CELL['slots'], CELL['max_blocks'] * CELL['block'],
        CELL['blocks'], CELL['block'])))
    return cfg, params, pool


_POOL = r'bf16\[2,2049,8,16,128\]'
_PLANE_ELEMS = 2049 * 8 * 16 * 128
_MOVES = ('copy', 'copy-start', 'copy-done', 'dynamic-slice',
          'dynamic-update-slice', 'slice', 'transpose', 'concatenate')


def _no_pool_is_taken_apart(hlo, writer):
    """No instruction PRODUCES a pool or a plane by moving it, under any
    shape of the same size (the flat views included): the pool enters as
    a parameter, rides the loops' tuples, is seen through bitcasts, and
    is written in place by ``writer`` and by nothing else:
    ``'fusion:scatter'`` (the row scatter's fusion: S > 1) or
    ``'paged_decode'`` (the decode kernel, whose pool results alias its
    operands: ``_kernel_call``)."""
    seen = set()
    for m in re.finditer(r'(%[\w.-]+) = bf16\[([\d,]+)\]\S* ([\w-]+)\('
                         r'([^\n]*)', hlo):
        name, dims, op, rest = m.groups()
        elems = 1
        for n in dims.split(','):
            elems *= int(n)
        if elems not in (_PLANE_ELEMS, 2 * _PLANE_ELEMS):
            continue
        if op == 'fusion':  # judged by what it is rooted in
            body = hlo[hlo.index(re.search(r'calls=(%[\w.-]+)',
                                           rest).group(1) + ' '):]
            op = 'fusion:' + re.search(r'ROOT %[\w.-]+ = \S+ ([\w-]+)\(',
                                       body).group(1)
        seen.add(op)
        assert op.split(':')[-1] not in _MOVES, (name, dims, op)
    # the write, in place
    assert ('fusion:scatter' in seen) == (writer == 'fusion:scatter'), seen
    if writer == 'paged_decode':
        _kernel_call(hlo)
    # the pool keeps its row-major layout from the arguments on
    assert set(re.findall(_POOL + r'\{([\d,]+)', hlo)) == {'4,3,2,1,0'}


def test_decode_step_hands_the_kernel_the_pool_as_it_lies(one_chip,
                                                          monkeypatch):
    """The whole decode chunk as the engine builds it (two layers of the
    cells' width). The pools ride the step scan and the layer scan as a
    carry; the kernel is handed BOTH WHOLE and a layer index, writes the
    step's row into them itself and hands them back as the SAME buffers
    (PR 31: the row scatter in front of it was 18% of the step; no
    scatter yields a pool any more). As ``xs``/``ys`` of the layer
    scan each layer's plane was sliced out, stacked back and the new
    pool copied into the step's carry (PR 28's ledger: 73% of the
    device's time in ``chat-steady``); and XLA lays a pool out for
    whatever touches it: an [H, D]-slab scatter had it re-laid as
    [NB, P, H, D] inside the loop (PR 26: 9 ms of a 38 ms step)."""
    # The backend is the CPU here; the program under test is the TPU's.
    monkeypatch.setattr(attention, '_use_pallas', lambda: True)
    cfg, params, pool = _llama_cell(one_chip)
    slots = CELL['slots']
    assert paged.decode_path(pool.tables.shape, pool.k.shape, pool.k.dtype,
                             False) == 'paged_kernel'

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (slots,), dtype,
                                    sharding=one_chip)

    hlo = engine_lib._jit_paged_chunk.lower(
        cfg, 2, params, pool, vec(jnp.int32), vec(jnp.float32), None, None,
        vec(jnp.bool_), vec(jnp.uint32, 2), None).compile().as_text()
    names = _kernel_call(hlo)
    defs = {m.group(1): (m.group(2), m.group(3)) for m in re.finditer(
        r'(%[\w.-]+) = (\S+) ([\w-]+)\(', hlo)}
    for name in names[6:]:
        shape, op = defs[name]
        assert re.match(_POOL, shape), (name, shape)
        assert op in ('bitcast', 'get-tuple-element', 'parameter'), (name,
                                                                     op)
    _no_pool_is_taken_apart(hlo, 'paged_decode')
    assert 'kernel-fallback' not in hlo


@pytest.mark.parametrize('width', [32, 256])
def test_shared_prefix_prefill_writes_the_pool_in_place(one_chip,
                                                        monkeypatch, width):
    """The suffix prefill over the pool (S = W, one row, the gather
    path), at two of the widths the engine warms: the same carry, so the
    tail's rows are scattered into the donated pool and the prefix is
    gathered out of it, block by block, with no plane in between."""
    monkeypatch.setattr(attention, '_use_pallas', lambda: True)
    cfg, params, pool = _llama_cell(one_chip)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo = paged.jit_prefill_shared.lower(
        cfg, params, pool, vec(jnp.int32, 1, width),
        vec(jnp.int32, 1, CELL['max_blocks']), vec(jnp.int32),
        vec(jnp.int32, 1), vec(jnp.int32, 1), None).compile().as_text()
    assert 'paged_decode' not in hlo
    _no_pool_is_taken_apart(hlo, 'fusion:scatter')


# -- the latent (MLA) cell: xing-docs-sessions -------------------------------

# 48 slots, max_len 4096 in blocks of 16 out of a pool of 8,193, 32 heads
# over one 512 + 64 latent row (stored 640 wide), bf16.
MLA_CELL = dict(slots=48, max_blocks=256, block=16, blocks=8193)


def _mla_cfg(layers=3):
    from skypilot_tpu.models import mla_moe
    return mla_moe.MlaMoeConfig(vocab_size=1024, n_layers=layers,
                                n_dense_layers=1, max_seq_len=32768)


def test_mla_decode_compiles_at_the_cells_geometry(one_chip):
    """One 576-number row a position pads to 640 in the HBM tiling
    whichever way it is stored; Mosaic refuses a 576-wide slice of it,
    so the pool states 640 (``decode_attention.latent_width``)."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    c = MLA_CELL
    assert decode_attention.latent_width(512, 64) == 640
    assert decode_attention.mla_fits(c['slots'], c['max_blocks'], c['block'],
                                     jnp.bfloat16)

    def compile_at(width):
        # skylint: allow-jit(test-only compile check)
        return jax.jit(lambda q, p, t, v: decode_attention.mla_decode(
            q, p, jnp.int32(1), t, v, 512, 0.1)).lower(
            sds((c['slots'], 32, 576), jnp.bfloat16),
            sds((2, c['blocks'], 1, c['block'], width), jnp.bfloat16),
            sds((c['slots'], c['max_blocks']), jnp.int32),
            sds((c['slots'],), jnp.int32)).compile()

    hlo = compile_at(640).as_text()
    assert re.search(r'%mla_decode[.\d]* = .*custom-call\(', hlo)
    with pytest.raises(Exception, match='aligned to tiling'):
        compile_at(576)


def test_mla_decode_step_hands_the_kernel_the_pool_as_it_lies(one_chip,
                                                              monkeypatch):
    """The whole decode chunk of the latent model as the engine builds
    it (one dense and two expert layers at the published widths): the
    kernel takes the WHOLE pool and a layer index, the row scatter
    updates the carried pool in place, and the experts' stacked weights
    reach the grouped matmul without a slice. So no copy and no layout
    change of the pool or of an expert stack stands in front of a Mosaic
    call (a sliced stack did: 64% of the step on the chip, PR 28)."""
    from skypilot_tpu.models import mla_moe
    monkeypatch.setattr(attention, '_use_pallas', lambda: True)
    cfg = _mla_cfg()

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    c = MLA_CELL
    slots = c['slots']
    params = on_chip(jax.eval_shape(
        lambda: mla_moe.init_params(jax.random.PRNGKey(0), cfg)))
    pool = on_chip(jax.eval_shape(lambda: mla_moe.init_pool(
        cfg, slots, c['max_blocks'] * c['block'], c['blocks'], c['block'])))
    assert pool.v is None and pool.k.shape == (3, 8193, 1, 16, 640)
    assert mla_moe.decode_path(pool.tables.shape, pool.k.shape,
                               pool.k.dtype) == 'mla_kernel'

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (slots,), dtype,
                                    sharding=one_chip)

    hlo = mla_moe.jit_paged_chunk.lower(
        cfg, 2, params, pool, vec(jnp.int32), vec(jnp.float32), None, None,
        vec(jnp.bool_), vec(jnp.uint32, 2), None).compile().as_text()
    # name -> (result type, op) of every instruction
    defs = {m.group(1): (m.group(2), m.group(3)) for m in re.finditer(
        r'(%[\w.-]+) = (\S+) ([\w-]+)\(', hlo)}
    calls = re.findall(r'%(mla_decode|ragged-dot-none)[.\d]* = [^\n]*'
                       r'custom-call\(([^)]*)\)', hlo)
    assert {name for name, _ in calls} == {'mla_decode', 'ragged-dot-none'}
    for name, operands in calls:
        # the pool, or a whole expert stack (2 layers x 64): the last
        # operand, reached as a view, never through a copy or a slice
        shape, op = defs[operands.split(',')[-1].strip()]
        want = (r'bf16\[3,8193,1,16,640\]' if name == 'mla_decode'
                else r'bf16\[128,\d+,\d+\]')
        assert re.match(want, shape), (name, shape)
        assert op in ('bitcast', 'get-tuple-element', 'parameter',
                      'copy-done'), (name, shape, op)
    # the pool keeps its row-major layout from the arguments to the call
    assert set(re.findall(r'bf16\[3,8193,1,16,640\]\{([\d,]+)', hlo)) == {
        '4,3,2,1,0'}
    assert 'kernel-fallback' not in hlo


def test_mla_prefill_takes_the_flash_kernel_at_the_cells_widest(one_chip,
                                                                monkeypatch):
    """A cold 2,048-token document pads to 4,096 positions: qk 192 wide
    puts S x D at 786k of the kernel's 1M cap, V rides zero-padded to
    192. No jnp fallback, and the program fits beside the weights."""
    from skypilot_tpu.models import mla_moe
    monkeypatch.setattr(attention, '_use_pallas', lambda: True)
    cfg = _mla_cfg(layers=2)
    assert attention._unsupported((1, 32, 4096, 192)) is None
    assert attention._unsupported((1, 32, 8192, 192)) is not None

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: mla_moe.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: mla_moe.init_cache(cfg, 1, 4096)))
    compiled = mla_moe.jit_prefill.lower(
        params, jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip),
        cache, cfg, jax.ShapeDtypeStruct((1,), jnp.int32,
                                         sharding=one_chip)).compile()
    hlo = compiled.as_text()
    assert re.search(r'%flash_fwd[.\d]* = .*custom-call\(', hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


# -- the hybrid (KDA + MLA) cell: kimi-linear-docs-steady --------------------

# 48 slots, max_len 4096 in blocks of 16 out of a pool of 12,289 for ONE
# latent layer; beside it a float32 state [32, 128, 128] a slot a KDA layer.
KDA_CELL = dict(slots=48, max_blocks=256, block=16, blocks=12289)


def _kda_cfg():
    """Four layers at Kimi-Linear-48B-A3B's widths: KDA + dense, KDA +
    experts, MLA + experts, KDA + experts; 128 of 256 experts held."""
    from skypilot_tpu.models import mla_moe
    return mla_moe.KdaMlaMoeConfig(
        vocab_size=1024, d_model=2304, n_layers=4, n_dense_layers=1,
        n_heads=32, q_lora_rank=None, d_ff=9216, num_experts=256,
        expert_top_k=8, routed_scale=2.446, experts_held=(0, 128),
        hc_mult=1, rope=False, rope_yarn=(1.0, 0, 0.0, 0.0, 1.0, 1.0),
        norm_eps=1e-5, max_seq_len=32768, kda_layers=(0, 1, 3))


def _top_level(hlo):
    """(name, result type, op) of every instruction that is not inside
    a fused computation: what has a buffer of its own."""
    out = []
    for block in re.split(r'\n(?=(?:ENTRY )?%[\w.-]+ \([^\n]*\) -> [^\n]*\{\n)',
                          hlo):
        if 'fused_computation' in block.split('\n', 1)[0]:
            continue
        out += re.findall(r'^\s*(?:ROOT )?(%[\w.-]+) = (\S+) ([\w-]+)\(',
                          block, re.M)
    return out


@pytest.mark.parametrize('trimmed', [False, True])
def test_kda_decode_step_carries_state_and_pool_in_place(one_chip,
                                                         monkeypatch,
                                                         trimmed):
    """The decode chunk of the hybrid model as the engine builds it: the
    state [L_kda, 48, 32, 128, 128] float32 and the tails ride the step
    scan and the layer scans as carries and are updated at the layer's
    index in place (a state that is ``xs``/``ys`` of a scan is copied
    whole every step: PR 29's lesson): ``decode_attention.kda_step``
    takes the whole state and a layer index and returns it aliased; no
    layer's state [48, 32, 128, 128] has a buffer of its own, none is
    sliced out in front of the call or put back behind it (PR 34: three
    XLA passes over all 48 slots went); the latent pool has ONE layer and
    reaches ``mla_decode`` whole; results alias the donated cache.
    ``trimmed``: the same of the chunk that stops after ``n_steps`` (a
    loop whose trip count is a device scalar, not a scan)."""
    from skypilot_tpu.models import mla_moe
    monkeypatch.setattr(attention, '_use_pallas', lambda: True)
    cfg = _kda_cfg()
    assert cfg.kinds == ('kda', 'kda', 'mla', 'kda')

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    c = KDA_CELL
    slots = c['slots']
    params = on_chip(jax.eval_shape(
        lambda: mla_moe.init_params(jax.random.PRNGKey(0), cfg)))
    pool = on_chip(jax.eval_shape(lambda: mla_moe.init_pool(
        cfg, slots, c['max_blocks'] * c['block'], c['blocks'], c['block'])))
    assert pool.k.shape == (1, 12289, 1, 16, 640)
    assert pool.state.shape == (3, 48, 32, 128, 128)
    assert pool.conv.shape == (3, 48, 3, 12288)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape or (slots,), dtype,
                                    sharding=one_chip)

    if trimmed:
        compiled = mla_moe.jit_paged_chunk_n.lower(
            cfg, 8, params, pool, vec(jnp.int32), vec(jnp.float32), None,
            None, vec(jnp.bool_), vec(jnp.uint32, 2),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    else:
        compiled = mla_moe.jit_paged_chunk.lower(
            cfg, 2, params, pool, vec(jnp.int32), vec(jnp.float32), None,
            None, vec(jnp.bool_), vec(jnp.uint32, 2), None).compile()
    hlo = compiled.as_text()
    whole, layer = r'f32\[3,48,32,128,128\]', r'f32\[48,32,128,128\]'
    for name, shape, op in _top_level(hlo):
        assert not re.match(layer, shape), (name, shape, op)
        # the whole state is a carry handed on and nothing else: no
        # fusion reads or updates it, nothing copies it
        if re.match(whole, shape):
            assert op in ('parameter', 'get-tuple-element', 'bitcast'), (
                name, shape, op)
    # ... but for ``kda_step`` (PR 34), one call a KDA layer, which
    # takes it as it lies and hands it back aliased
    defs = {m.group(1): (m.group(2), m.group(3)) for m in re.finditer(
        r'(%[\w.-]+) = (\S+) ([\w-]+)\(', hlo)}
    calls = re.findall(r'%kda_step[.\d]* = (\([^\n]*?\)) custom-call\('
                       r'([^)]*)\)([^\n]*)', hlo)
    assert len(calls) == 3, len(calls)
    for result, operands, rest in calls:
        assert re.fullmatch(r'\(f32\[48,32,128\]\S*, ' + whole + r'\S*\)',
                            result), result
        assert re.search(r'output_to_operand_aliasing=\{\{1\}: \(4, \{\}\)\}',
                         rest), rest[:300]
        shape, op = defs[operands.split(',')[-1].strip()]
        assert re.match(whole, shape), shape
        assert op in ('parameter', 'get-tuple-element', 'bitcast'), op
    assert set(re.findall(whole + r'\{([\d,]+)', hlo)) == {'4,3,2,1,0'}
    stats = compiled.memory_analysis()
    kept = sum(x.size * x.dtype.itemsize for x in (pool.k, pool.state,
                                                   pool.conv))
    assert stats.alias_size_in_bytes >= kept
    assert stats.temp_size_in_bytes < 0.2e9      # no second state (0.3 GB)
    assert re.search(r'%mla_decode[.\d]* = [^\n]*custom-call\(', hlo)
    assert set(re.findall(r'bf16\[1,12289,1,16,640\]\{([\d,]+)', hlo)) == {
        '4,3,2,1,0'}
    assert 'kernel-fallback' not in hlo


def test_kda_step_compiles_at_the_cells_geometry(one_chip):
    """48 slots x 32 heads of [128, 128] float32, four layers: the
    row's [32, 128, 128] buffer (2 MB) and the blocks of eight rows fit
    VMEM, a head's decay, k and q turn from lanes onto sublanes by a
    [3, 128] transpose Mosaic takes, and the state is operand 4 and
    result 1 of one call."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, h, d = KDA_CELL['slots'], 32, 128
    assert decode_attention.kda_fits((4, b, h, d, d), jnp.float32)
    vec = sds((b, h, d))
    # skylint: allow-jit(test-only compile check)
    compiled = jax.jit(lambda *a: decode_attention.kda_step(*a),
                       donate_argnums=(0,)).lower(
        sds((4, b, h, d, d)), sds((), jnp.int32), vec, vec, vec, vec,
        sds((b, h)), sds((b,), jnp.bool_)).compile()
    hlo = compiled.as_text()
    call = re.search(r'%kda_step[.\d]* = [^\n]*custom-call\(([^)]*)\)'
                     r'([^\n]*)', hlo)
    assert call, 'no kda_step call'
    assert re.search(r'output_to_operand_aliasing=\{\{1\}: \(4, \{\}\)\}',
                     call.group(2))
    assert not re.search(r'= f32\[4,48,32,128,128\]\S* (copy|fusion)\(', hlo)
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= 4 * b * h * d * d * 4
    assert stats.temp_size_in_bytes < 16e6


def test_kda_group_prefill_fits_beside_the_weights(one_chip, monkeypatch):
    """A group of 4 rows padded to 4,096: the MLA layer takes the flash
    kernel, and the KDA layers go a row at a time, so the chunked form's
    float32 temporaries are one row's (4.5 GB for the group at once,
    which 9.3 GB of weights leave no room for)."""
    from skypilot_tpu.models import mla_moe
    monkeypatch.setattr(attention, '_use_pallas', lambda: True)
    cfg = _kda_cfg()

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: mla_moe.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: mla_moe.init_cache(cfg, 4, 4096)))
    compiled = mla_moe.jit_prefill.lower(
        params, jax.ShapeDtypeStruct((4, 4096), jnp.int32, sharding=one_chip),
        cache, cfg, jax.ShapeDtypeStruct((4,), jnp.int32,
                                         sharding=one_chip)).compile()
    assert re.search(r'%flash_fwd[.\d]* = .*custom-call\(', compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


def test_kda_prefill_piece_continues_a_scratch_row(one_chip, monkeypatch):
    """One piece of the chunked long prefill as the engine runs it: 512
    tokens of ONE row continuing a scratch row 4,096 wide (state, tails
    and the latent rows so far). The MLA layer attends over the row's
    view (no flash kernel: that is the fresh prefill's), the map over
    rows is gone for the one row (the only loops left are the layer scan
    and the KDA layers' chunk scans), and the temporaries are a fraction
    of the group prefill's."""
    from skypilot_tpu.models import mla_moe, model_ops
    monkeypatch.setattr(attention, '_use_pallas', lambda: True)
    cfg = _kda_cfg()
    piece = model_ops.ops_for(cfg).prefill_chunk(cfg)
    assert piece == 512

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: mla_moe.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: mla_moe.init_cache(cfg, 1, 4096)))
    compiled = mla_moe.jit_prefill.lower(
        params, jax.ShapeDtypeStruct((1, piece), jnp.int32,
                                     sharding=one_chip),
        cache, cfg, jax.ShapeDtypeStruct((1,), jnp.int32,
                                         sharding=one_chip)).compile()
    hlo = compiled.as_text()
    assert 'flash_fwd' not in hlo
    # layers 0 | 1 | 2 | 3 are runs of their own here (KDA + dense, KDA +
    # experts, MLA, KDA + experts): three chunk scans, no scan of rows
    assert len(re.findall(r' while\(', hlo)) == 3
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9
