"""Architecture registry: ``--arch <id>`` resolution for launch/dryrun/tests."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import ArchSpec

_MODULES = {
    "qwen3-8b": "repro.configs.qwen3_8b",
    "qwen3-4b": "repro.configs.qwen3_4b",
    "granite-8b": "repro.configs.granite_8b",
    "qwen3-moe-30b-a3b": "repro.configs.qwen3_moe_30b_a3b",
    "deepseek-v2-lite-16b": "repro.configs.deepseek_v2_lite_16b",
    "meshgraphnet": "repro.configs.meshgraphnet",
    "two-tower-retrieval": "repro.configs.two_tower_retrieval",
    "dcn-v2": "repro.configs.dcn_v2",
    "dien": "repro.configs.dien",
    "bert4rec": "repro.configs.bert4rec",
    "hstu": "repro.configs.hstu",
    "dlrm-uih": "repro.configs.dlrm_uih",
}

# the 10 assigned archs (dlrm-uih is the paper's own and hstu the generative
# recommender the ultra-long histories feed, both listed separately)
ASSIGNED: List[str] = [a for a in _MODULES if a not in ("dlrm-uih", "hstu")]


def get_arch(arch_id: str) -> ArchSpec:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(_MODULES[arch_id]).spec()


def list_archs(include_paper_own: bool = True) -> List[str]:
    return list(_MODULES) if include_paper_own else list(ASSIGNED)
