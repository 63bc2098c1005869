"""Vectorized ingest engine: the batch marshal subsystem.

The device verifies thousands of sets per second, but a scalar host feed
path re-does per-set Python work — hashing, pubkey aggregation, limb
encode — for every signature set and pins one core.  This package makes
operand preparation a first-class subsystem in front of the wide verify
unit:

* :mod:`.cache` — device-resident pubkey limb cache (registry tier keyed
  by validator index + epoch-scoped aggregate LRU): repeat signers skip
  aggregation and limb encode;
* :mod:`.pool` — core-scaling shard pool for numpy stages (no stage
  uses it today);
* :mod:`.engine` — :class:`IngestEngine`, the never-raise
  ``marshal_sets`` front-end, byte-identical to the scalar oracle and
  degrading to it on any failure.

Wire into the pipeline via
``PipelinedVerifier.for_backend(..., ingest=engine)`` or use
``engine.marshal_sets`` anywhere a marshal callable is expected.
"""

from .cache import PubkeyLimbCache
from .engine import IngestEngine
from .pool import MarshalPool

__all__ = ["IngestEngine", "MarshalPool", "PubkeyLimbCache"]
