"""Distributed execution utilities: sharding specs."""
from repro.dist.sharding import (batch_specs, emb_specs, emb_state_specs,
                                 replicated, state_specs, to_named)

__all__ = [
    "batch_specs",
    "emb_specs",
    "emb_state_specs",
    "replicated",
    "state_specs",
    "to_named",
]
