"""The driver's single-chip entry point proves the serving path.

`__graft_entry__.entry()` hands the driver a function to compile. It has
to be the program the server jits — a `mixed` step over flat rows with
block tables, K/V written into the pool and attended through the fused
seam — not a prompt-phase forward no runner can build.
"""
import jax
import numpy as np

import __graft_entry__ as graft
from intellillm_tpu.obs import get_compile_tracker


def test_entry_is_a_mixed_step_through_the_fused_seam():
    fn, args = graft.entry()
    before = dict(get_compile_tracker().snapshot()["kernel_dispatch"])
    lowered = jax.jit(fn).lower(*args)
    after = get_compile_tracker().snapshot()["kernel_dispatch"]
    took = {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}
    # One trace of the fused write + attend a layer (the oracle here: the
    # tiny model's heads are 16 wide), and no other attention.
    assert took == {"reference:ragged": 2}, took
    assert "scatter" in lowered.as_text()

    params, kv_caches, token_ids, positions, tables, context_lens = args[:6]
    packed, new_caches = jax.jit(fn)(*args)
    assert packed.shape[0] == token_ids.shape[0] == 32
    live = np.asarray(context_lens) > 0
    assert live.sum() == 23 and not live[23:].any()
    # Every live row wrote its token's K/V at its block table's slot for
    # its position, in every layer, and a pad row wrote nowhere.
    pos, bs = np.asarray(positions)[:, 0], kv_caches[0][0].shape[2]
    block = np.asarray(tables)[np.arange(32), pos // bs]
    written = np.zeros(kv_caches[0][0].shape[::2], bool)   # [blocks, bs]
    written[block[live], pos[live] % bs] = True
    for k_pool, v_pool in new_caches:
        for pool in (k_pool, v_pool):
            touched = np.abs(np.asarray(pool)).sum(axis=(1, 3)) > 0
            np.testing.assert_array_equal(touched, written)


def test_dryrun_multichip_steps_dense_and_moe_over_the_mesh(capsys):
    import pytest
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (virtual) devices")
    graft.dryrun_multichip(8)
    assert "dryrun_multichip OK: mesh=(data=2, model=4)" in (
        capsys.readouterr().out)
