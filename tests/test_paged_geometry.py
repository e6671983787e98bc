"""The paged layout at a geometry where head_dim != block size. Every
tiny preset has head_dim 16 == the default KV block of 16, which hid a
gather that read the block size from the head_dim axis until the first
run at bench-1b's width (head_dim 128) on the chip (chip_smoke.py,
PR 21)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.models import engine as engine_lib
from skypilot_tpu.models import generate, llama


def test_paged_engine_with_head_dim_unlike_the_block():
    cfg = dataclasses.replace(llama.TINY, head_dim=32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = engine_lib.ContinuousEngine(params, cfg, slots=2, max_len=64,
                                      chunk_steps=4)
    assert eng.kv_block != cfg.head_dim
    eng.start()
    try:
        rows = [[5, 6, 7], [8, 9, 10, 11, 12], [5, 6, 7]]
        futs = [eng.submit(r, 6) for r in rows]
        for row, fut in zip(rows, futs):
            want = generate.generate(
                params, cfg, jnp.asarray([row], jnp.int32),
                max_new_tokens=6, max_len=64)
            assert fut.result(timeout=180) == np.asarray(want[0]).tolist()
        assert eng.stats()['failures'] == 0
    finally:
        eng.stop()
