"""The result line, and the numbers compared with their limits."""
from __future__ import annotations

import json
import math
import sys


def device_info(devs, chips: int) -> dict:
    used = devs[:chips]
    peak = 0
    for d in used:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def checks_ok(checks: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def emit(*, correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, checks: dict, breakdown=None) -> None:
    """Standard error ends with each number compared beside its limit;
    standard output ends with the one JSON result line, whose last key
    holds the same numbers."""
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    print(json.dumps(line), flush=True)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def bytes_in_use(dev) -> int:
    return int((dev.memory_stats() or {}).get("bytes_in_use", -1))
