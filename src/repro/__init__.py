"""repro — parallel-sort reproduction framework.

Public façade: ``repro.sort`` (the autotuned front door over the paper's four
models) and the ``repro.engine`` subpackage (plans, key–value sorting, the
batched serving service).  See ``docs/architecture.md`` for the layer map.
"""
from repro.core.api import sort

__all__ = ["sort"]
