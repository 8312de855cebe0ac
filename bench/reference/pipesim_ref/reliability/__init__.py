"""Reliability subsystem: correlated failure domains, repair queues, spot
eviction, and checkpointed retrains, compiled into the engines' control
stage (see :mod:`pipesim_ref.reliability.specs` for the declarative layer and
:mod:`pipesim_ref.reliability.compile` for the tensor lowering)."""
from pipesim_ref.reliability.compile import (CompiledReliability, RelEvent,
                                       check_no_double_apply,
                                       compile_reliability)
from pipesim_ref.reliability.specs import (CheckpointSpec, DomainOutageModel,
                                     ReliabilitySpec, RepairSpec,
                                     SpotPoolSpec, TopologySpec)

__all__ = [
    "TopologySpec", "DomainOutageModel", "RepairSpec", "SpotPoolSpec",
    "CheckpointSpec", "ReliabilitySpec", "CompiledReliability", "RelEvent",
    "compile_reliability", "check_no_double_apply",
]
