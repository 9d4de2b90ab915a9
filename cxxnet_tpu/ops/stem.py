"""In-step uint8 decode-normalize (trainer ``input_fold``).

The ``device_normalize`` input path ships uint8 batches (4x smaller
H2D) and normalizes on-device — but as a SEPARATE jitted dispatch that
reads the uint8 batch and writes a full fp32 copy the train step then
re-reads. Per pixel that is 1 (u8 read) + 4 (f32 write) + 4 (f32 step
read) = 9 bytes before the stem conv sees anything.

This op is the in-step replacement: the uint8 batch enters the train
step directly and the cast/mean-subtract/scale happens inside the
compiled step, emitting the stem conv's input in the compute dtype —
1 (u8 read) + compute-dtype write, with XLA free to fuse the write into
the space-to-depth producer chain (layers/conv.py). The fp32 round-trip
of the whole input batch is gone; at flagship shape (256x224x224x3)
that is ~310 MB of HBM traffic per step.

Numerics: the fold computes in f32 and casts ONCE to the compute dtype
— under an fp32 policy this is bit-identical to the eager
``_device_normalize`` path; under bf16/fp16 the input enters the model
already rounded to the compute dtype, which is exactly where the
layers' own ``astype(ctx.compute_dtype)`` puts it one op later.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp


def decode_normalize(x: jax.Array, mean: Optional[jax.Array], factor,
                     out_dtype: Any) -> jax.Array:
    """Trainer._device_normalize's math (cast, subtract mean, scale)
    with the output in ``out_dtype``. ``mean`` broadcasts over the
    trailing axes: per-channel (C,) or a mean image (H, W, C).
    ``factor`` may be a traced scalar."""
    y = x.astype(jnp.float32)
    if mean is not None:
        y = y - mean
    y = y * factor
    return y.astype(out_dtype)
