"""The least bytes a mesh exchange has to move, from its spans alone: the
same work whatever implements it.

Every live row is read once where it is sent from and written once where it
arrives, its values and their validity bytes: ``2 x rows x row_bytes`` of
HBM traffic over the mesh. ``rows`` and ``row_bytes`` are the counts of the
program's ``MeshExchange.collective`` spans (rows that entered the exchange;
a column's value bytes plus one validity byte). Padding to the shards'
capacity, the compaction's passes and the all_to_all's own buffers are what
an implementation adds on top, and are not counted.
"""

COLLECTIVE = "MeshExchange.collective"
HOST_HOP = {"MeshExchange.map": "d2h_bytes", "MeshExchange.ingest": "h2d_bytes"}


def least_exchange_bytes(spans) -> int:
    """Summed over the ``MeshExchange.collective`` spans of ``spans``."""
    return sum(2 * s["counts"]["rows"] * s["counts"]["row_bytes"]
               for s in spans if s["name"] == COLLECTIVE
               and "rows" in s["counts"] and "row_bytes" in s["counts"])


def host_hop_bytes(spans) -> int:
    """Device-to-host bytes of the map sides plus host-to-device bytes of
    the ingests: what crossed the host between a child and its exchange."""
    return sum(s["counts"].get(HOST_HOP[s["name"]], 0)
               for s in spans if s["name"] in HOST_HOP)
