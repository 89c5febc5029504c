from .lbm_collide import resolve_interpret
from .ops import fused_stream_collide
from .ref import stream_collide_ref

__all__ = [
    "fused_stream_collide",
    "resolve_interpret",
    "stream_collide_ref",
]
