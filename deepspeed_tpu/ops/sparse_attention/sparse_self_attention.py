"""Block-sparse self attention.

Reference: ``deepspeed/ops/sparse_attention/sparse_self_attention.py:12
SparseSelfAttention`` + the Triton ``matmul.py``/``softmax.py`` block
kernels. TPU path: the Pallas splash kernel (``splash.py``) consumes the
block layout as a scalar-prefetched block table and SKIPS masked tiles —
compute ∝ active blocks, matching the Triton SDD/DSD capability; the
masked dense einsum here is the fallback (padding masks, odd shapes) and
the numerics oracle.
"""

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..registry import registry
from .sparsity_config import SparsityConfig, FixedSparsityConfig


def layout_to_mask(layout: np.ndarray, block: int) -> np.ndarray:
    """[heads, nb, nb] block layout → [heads, seq, seq] boolean mask."""
    return np.kron(layout, np.ones((block, block), dtype=np.int64)).astype(bool)


def sparse_attention(q, k, v, layout: np.ndarray, block: int,
                     key_padding_mask: Optional[jnp.ndarray] = None,
                     scale: Optional[float] = None,
                     key_padding_mask_mode: str = "mul",
                     use_kernel: Optional[bool] = None):
    """Masked attention under a block-sparse layout.
    q,k,v: [batch, heads, seq, head_dim]; layout: [heads, nb, nb].
    key_padding_mask [b, s]: mode 'mul' = keep-mask (True/1 = attend);
    mode 'add' = additive float mask (0 = keep, large-negative = drop) —
    the reference's two conventions (sparse_self_attention.py:12).

    On TPU (no padding mask) this dispatches to the Pallas splash kernel
    (splash.py), whose compute scales with ACTIVE blocks; the dense masked
    einsum is the fallback/oracle. use_kernel forces either path."""
    b, h, s, d = q.shape
    scale = scale if scale is not None else (1.0 / float(np.sqrt(d)))
    if use_kernel and key_padding_mask is not None:
        raise ValueError("the splash kernel does not take key_padding_mask; "
                         "fold padding into the layout or use the dense path")
    if use_kernel is None:
        from ..registry import on_tpu
        use_kernel = (key_padding_mask is None and s % block == 0
                      and on_tpu())
    if use_kernel:
        from .splash import splash_sparse_attention
        return splash_sparse_attention(q, k, v, layout, block, scale=scale)
    visible = jnp.asarray(layout_to_mask(layout, block))[None]  # [1, h, s, s]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    neg = jnp.finfo(jnp.float32).min
    if key_padding_mask is not None:
        kpm = key_padding_mask[:, None, None, :]
        if key_padding_mask_mode == "add" and kpm.dtype != jnp.bool_:
            # purely additive (reference semantics): moderate biases (e.g.
            # ALiBi-style values ≤ -1) must bias, not hard-mask — only the
            # sparse layout decides visibility here
            scores = scores + kpm.astype(jnp.float32)
        else:  # keep-mask (bool is always keep-style, whatever the mode)
            visible = visible & kpm.astype(bool)
    scores = jnp.where(visible, scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    # rows with no visible key at all would softmax to uniform; zero them
    any_visible = visible.any(-1, keepdims=True)
    probs = jnp.where(any_visible, probs, 0.0).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


class SparseSelfAttention:
    """Reference-parity wrapper: config-held layout, __call__(q, k, v)."""

    def __init__(self, sparsity_config: Optional[SparsityConfig] = None,
                 key_padding_mask_mode: str = "add", attn_mask_mode: str = "mul"):
        self.sparsity_config = sparsity_config or FixedSparsityConfig(num_heads=4)
        self.key_padding_mask_mode = key_padding_mask_mode
        self.attn_mask_mode = attn_mask_mode
        self._layout_cache = {}

    def get_layout(self, seq_len: int) -> np.ndarray:
        if seq_len not in self._layout_cache:
            self._layout_cache[seq_len] = self.sparsity_config.make_layout(seq_len)
        return self._layout_cache[seq_len]

    def __call__(self, query, key, value, key_padding_mask=None):
        s = query.shape[2]
        layout = self.get_layout(s)
        return sparse_attention(query, key, value, layout,
                                self.sparsity_config.block, key_padding_mask,
                                key_padding_mask_mode=self.key_padding_mask_mode)


registry.register("sparse_attention", "pallas", True,
                  "splash block-sparse kernel, sparse fwd AND bwd (dq via "
                  "forward block table, dk/dv via transposed table); "
                  "masked-dense XLA fallback via use_kernel=False")
