"""What every architecture's work counts (``bench/models/<architecture>/
work.py``) share.

Operations and bytes that the work requires, from the shapes alone, are
counted once per use of the model, whatever the program recomputes: a
matmul of (M, K) by (K, N) is 2MKN operations; causal attention of a query
at position p reads keys 0..p. Recompute under remat, padding of a fixed
(slots, chunk) block and the frozen base's weight gradients are not
required work and are not counted. Bytes are bf16 (2 per element) unless
said otherwise.
"""
from __future__ import annotations

BF16 = 2


def causal_pairs(t: int) -> float:
    """Query-key pairs of causal attention over ``t`` tokens."""
    return t * (t + 1) / 2.0
