"""TPU-native operator library.

One Pallas kernel family lives here, flash attention
(``attention.py``), paired with its jnp references and validated by
golden tests (the pairtest idea, SURVEY §4); it is selected by what the
code observes (``attn_impl = auto``). ``stem.py`` and ``quant.py`` are
plain jnp ops shared by more than one caller, and ``fused.py`` is the
model's selection log.
"""

from .attention import (
    attention_reference,
    chunked_attention,
    flash_attention,
    rope,
)

__all__ = [
    "attention_reference",
    "chunked_attention",
    "flash_attention",
    "rope",
]
