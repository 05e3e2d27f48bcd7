"""Generator ``scenario``: one model's packets from one scenario.

Mix parameters: ``scenario`` (a name in ``library.SCENARIOS``) and
``pool_packets``.  The pool is ``(None, bits)``, ``bits`` of shape
``(pool_packets, input bits)`` int32 in {0,1}.
"""
from __future__ import annotations

from traffic import library


def pool(mix: dict, tenants: list, seed: int):
    if len(tenants) != 1:
        raise ValueError(f"generator 'scenario' serves one model, got {len(tenants)}")
    width = tenants[0]["shape"][0]
    bits = library.SCENARIOS[mix["scenario"]].generate(mix["pool_packets"], width, seed)
    return None, bits
