"""Which devices hold a run's arrays, and what their memory reports.

The entry points (cli.train's summary JSON, cli.serve's start-up line)
name the device their results came from by reading it off the arrays
themselves — not off `jax.devices()[0]`, which says what the process could
have used, not what it did.  A driver that starts them as children (one
process per chip) has no other way to see the device.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional

import jax


def device_summary(arrays: Iterable[jax.Array]) -> Dict[str, object]:
    """{"platform", "kind", "count"} over the distinct devices holding
    `arrays` (a mesh-replicated or -sharded array counts every device it
    lives on)."""
    devices = sorted({d for a in arrays for d in a.devices()},
                     key=lambda d: d.id)
    if not devices:
        raise ValueError("device_summary needs at least one device array")
    kinds = {(d.platform, d.device_kind) for d in devices}
    if len(kinds) != 1:
        raise ValueError(f"arrays span unlike devices: {sorted(kinds)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def device_memory() -> List[Dict[str, Optional[int]]]:
    """Per local device: the bytes of the arrays alive on it (counted from
    their shards, so it works on every backend), and bytes in use now and
    at the peak as the backend reports them (`memory_stats()` is None on
    the CPU backend, so both are None there; on a TPU neither is)."""
    live = {d.id: 0 for d in jax.local_devices()}
    seen = set()
    for a in jax.live_arrays():
        for shard in a.addressable_shards:
            # two Array objects can share one buffer (device_put of an
            # array already in place): count the buffer once
            key = (shard.device.id, shard.data.unsafe_buffer_pointer())
            if key not in seen:
                seen.add(key)
                live[shard.device.id] += shard.data.nbytes
    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out.append({"id": d.id,
                    "live_array_bytes": live[d.id],
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out
