"""The latent-attention MoE decoder (Kimi K2's block, the DeepSeek-V3
block): multi-head latent attention with YaRN, a leading dense layer, then
expert layers routed by sigmoid scores with a correction bias over all
experts, of which this chip holds a share, plus a shared expert; an untied
head. ``reference.py`` writes it down.

The package holds what every architecture's does (see
``bench/models/dense_decoder/__init__.py``): ``weights``, ``reference``
(``Reference``), ``work`` (``train_step``) and ``seam``.
"""
from . import reference, seam, weights, work
from .reference import Reference

__all__ = ["Reference", "reference", "seam", "weights", "work"]
