"""Published inter-chip interconnect (ICI) rate of the chips a mesh cell may
run on, keyed like ``peaks.py`` by the ``device_kind`` JAX reports; a kind
that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
interchip interconnect bandwidth 1,600 Gbit/s a chip (all of a chip's links
together), = 200 GB/s. A 2x2 host gives a chip two neighbours, so a
collective there cannot reach it: a share of this rate is a share of what
the chip's interconnect could carry, not of what this topology can.

The bytes a collective has to move are counted here too, from the
configuration alone: never from what the program reports.
"""

from __future__ import annotations

ICI = {
    "v5 lite": {"ici_bytes_per_s": 1600e9 / 8, "source": "Google Cloud documentation, TPU v5e"},
    "v5e": {"ici_bytes_per_s": 1600e9 / 8, "source": "Google Cloud documentation, TPU v5e"},
}


def ici_for(device_kind: str) -> dict:
    kind = str(device_kind).lower()
    for tag, row in ICI.items():
        if tag in kind:
            return row
    raise KeyError(
        f"no published ICI rate for device_kind {device_kind!r}; add a row to "
        "benchmark/peaks_ici.py with its source"
    )


def table_bytes(config: dict) -> int:
    """Both float32 factor tables of a fit configuration."""
    return (config["n_users"] + config["n_items"]) * config["rank"] * 4


def assembly_bytes_into_a_chip(config: dict) -> float:
    """What a row-sharded sweep REQUIRES a chip to receive so that each
    source table is whole on it once: the other chips' shards of both."""
    n = config["mesh_devices"]
    return table_bytes(config) * (n - 1) / n


def least_assembly_seconds(config: dict, device_kind: str) -> float:
    """The least time a chip's interconnect could take for one sweep's
    assemblies."""
    return assembly_bytes_into_a_chip(config) / ici_for(device_kind)["ici_bytes_per_s"]
