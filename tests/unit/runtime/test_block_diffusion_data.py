"""The block-diffusion objective's data transform
(``runtime/data_pipeline/block_diffusion.py``): deterministic in its seed,
the masked share follows the noise level, weights are ``1/t`` of the block,
the clean copy never holds the mask id."""

import numpy as np
import pytest

from deepspeed_tpu.runtime.data_pipeline import (BlockDiffusionNoiser, DiffusionBatch,
                                                 noise_batch)

MASK, BLOCK = 999, 4


def _ids(rows=4, seq=4096, seed=0):
    return np.random.default_rng(seed).integers(0, MASK, (rows, seq), dtype=np.int32)


def test_the_batch_holds_what_the_model_takes():
    ids = _ids(2, 64)
    b = noise_batch(ids, 7, BLOCK, MASK)
    assert isinstance(b, DiffusionBatch)
    assert b.input_ids.shape == (2, 128) and b.input_ids.dtype == np.int32
    assert b.positions.shape == (2, 128) and b.weights.shape == b.targets.shape == (2, 64)
    np.testing.assert_array_equal(b.input_ids[:, 64:], ids)       # x0, clean
    np.testing.assert_array_equal(b.targets, ids)                 # no shift
    np.testing.assert_array_equal(b.positions[0], np.tile(np.arange(64), 2))
    xt, masked = b.input_ids[:, :64], b.weights > 0
    assert (xt[masked] == MASK).all() and (xt[~masked] == ids[~masked]).all()
    assert not (b.input_ids[:, 64:] == MASK).any()    # ids never equal the mask id
    args, kwargs = b.model_args()
    assert args[0] is b.input_ids and args[1] is b.targets
    assert set(kwargs) == {"positions", "loss_weights"}


def test_same_seed_same_batch_and_another_seed_another():
    ids = _ids(2, 256)
    a, b = noise_batch(ids, [3, 5], BLOCK, MASK), noise_batch(ids, [3, 5], BLOCK, MASK)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    other = noise_batch(ids, [3, 6], BLOCK, MASK)
    assert (other.weights != a.weights).any()
    noiser = BlockDiffusionNoiser(BLOCK, MASK, seed=3)
    for x, y in zip(noiser(ids, 5), a):         # step n's draw, whatever came before
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("block", [4, 8, 32])
def test_weights_are_one_over_t_of_the_block_and_the_masked_share_follows_t(block):
    t_min = 1e-3
    b = noise_batch(_ids(4, 8192), 11, block, MASK, t_min)
    w = b.weights.reshape(4, -1, block)
    for blk in w.reshape(-1, block)[:2000]:
        live = blk[blk > 0]
        assert live.size == 0 or np.all(live == live[0])   # one t a block
    t = 1.0 / w[w > 0]
    assert t.min() >= t_min * (1 - 1e-6) and t.max() <= 1.0
    # E[masked share] = E[t] = (1 + t_min) / 2; E[weight] = E[1[masked] / t] = 1
    assert abs((b.weights > 0).mean() - (1 + t_min) / 2) < 0.03
    assert abs(b.weights.mean() - 1.0) < 0.25       # heavy-tailed: E[w^2] = E[1/t] = 6.9
    # among blocks with some token masked, a higher t masks more of the block
    share = (w > 0).mean(axis=-1).ravel()
    level = np.where(share > 0, 1.0 / np.maximum(w.max(axis=-1).ravel(), 1e-9), np.nan)
    seen = ~np.isnan(level)
    assert np.corrcoef(level[seen], share[seen])[0, 1] > 0.5


def test_tokens_that_are_not_whole_blocks_are_refused():
    with pytest.raises(ValueError, match="whole blocks"):
        noise_batch(_ids(1, 30), 0, BLOCK, MASK)
