# repro.api — the unified SemanticBBV service surface.
#   store.py      SignatureStore: device-resident signatures + lifecycle
#   knowledge.py  KnowledgeBase: build/attach/estimate over archetypes
#   lifecycle.py  EvictionPolicy / vacuum: TTL+LRU eviction, compaction
#   service.py    SemanticBBVService facade + typed ServiceConfig
from repro.api.knowledge import (
    CPIEstimate, KnowledgeBase, assign_signatures,
)
from repro.api.lifecycle import (
    EvictionPolicy, VacuumReport, select_victims, vacuum,
)
from repro.api.service import SemanticBBVService, ServiceConfig
from repro.api.store import SignatureStore

__all__ = [
    "CPIEstimate", "EvictionPolicy", "KnowledgeBase", "SemanticBBVService",
    "ServiceConfig", "SignatureStore", "VacuumReport", "assign_signatures",
    "select_victims", "vacuum",
]
