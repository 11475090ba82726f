"""The block-diffusion objective's data transform (BD3-LM's efficient
training form, which SDAR's training follows).

A batch of documents ``x0`` [rows, L] is cut into blocks of ``block_length``
tokens. Each block draws its own noise level ``t ~ U(t_min, 1]``, and each
of its tokens is replaced by ``mask_id`` with probability ``t``, which gives
``xt``. The model runs once on ``xt ⊕ x0`` (``LlamaConfig.objective ==
"block_diffusion"``): both copies carry their tokens' own position ids, the
targets are ``x0`` itself (no shift) and a token's weight is ``1 / t`` of
its block where it was masked, else 0. On the host, in numpy, and a pure
function of ``(ids, seed, step)``.
"""

from typing import NamedTuple

import numpy as np


class DiffusionBatch(NamedTuple):
    """What ``LlamaForCausalLM`` takes under the block-diffusion objective
    (``model_args`` gives it in the module's own order)."""
    input_ids: np.ndarray       # [rows, 2L] int32: xt, then x0
    targets: np.ndarray         # [rows, L] int32: x0
    positions: np.ndarray       # [rows, 2L] int32: 0..L-1 twice
    weights: np.ndarray         # [rows, L] float32: 1/t where masked, else 0

    def model_args(self) -> tuple:
        """``(args, kwargs)`` of the model's call."""
        return ((self.input_ids, self.targets),
                {"positions": self.positions, "loss_weights": self.weights})


def noise_batch(ids, seed, block_length: int, mask_id: int,
                t_min: float = 1e-3) -> DiffusionBatch:
    """``ids`` [rows, L] (L a multiple of ``block_length``, no id equal to
    ``mask_id``) -> the model's batch. ``seed``: an int or a sequence of
    ints (``[seed, step]``), as ``numpy.random.default_rng`` takes it."""
    x0 = np.asarray(ids, np.int32)
    rows, seq = x0.shape
    if seq % block_length:
        raise ValueError(f"{seq} tokens are not whole blocks of {block_length}")
    rng = np.random.default_rng(seed)
    # U(t_min, 1]: 1 - u is in (0, 1] for u in [0, 1)
    t = t_min + (1.0 - t_min) * (1.0 - rng.random((rows, seq // block_length)))
    t = np.repeat(t, block_length, axis=1).astype(np.float32)
    masked = rng.random((rows, seq), dtype=np.float32) < t
    xt = np.where(masked, np.int32(mask_id), x0)
    positions = np.broadcast_to(np.tile(np.arange(seq, dtype=np.int32), 2),
                                (rows, 2 * seq))
    return DiffusionBatch(np.concatenate([xt, x0], axis=1), x0, positions,
                          np.where(masked, 1.0 / t, 0.0).astype(np.float32))


class BlockDiffusionNoiser:
    """``noise_batch`` with its parameters bound, one draw a step: the noise
    of step ``n`` is ``default_rng([seed, n])``, whatever was drawn before."""

    def __init__(self, block_length: int, mask_id: int, t_min: float = 1e-3,
                 seed: int = 0):
        self.block_length, self.mask_id = int(block_length), int(mask_id)
        self.t_min, self.seed = float(t_min), int(seed)

    def __call__(self, ids, step: int) -> DiffusionBatch:
        return noise_batch(ids, [self.seed, int(step)], self.block_length,
                           self.mask_id, self.t_min)
