"""The dense decoder: pre-norm attention and SwiGLU blocks with a tied
readout, as ``reference.py`` writes it down.

An architecture package holds ``weights.py`` (its seeded weights, and
``adaptable``, the shapes the adapter is sized from), ``reference.py``
(``Reference``, the plain float32 reference), ``work.py`` (``train_step``
and ``serve_forward``, the work the readers in ``bench/metrics/`` count)
and ``seam.py`` (``model_config``, ``params`` and ``counts``, the only
bridge to the program). ``bench.lib.cells.architecture`` loads it by the
name in a configuration file's ``architecture`` key.
"""
from . import reference, seam, weights, work
from .reference import Reference

__all__ = ["Reference", "reference", "seam", "weights", "work"]
